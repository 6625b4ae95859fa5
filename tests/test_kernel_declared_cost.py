"""What each Pallas call of the package declares of its own work
(`cost_estimate`, ops/kernel_cost.py), read off the traced call at a
small shape and held to a count written out here; how the one parse
(`xplane.hlo_instructions`) reads a declaration out of compiled text; and
what `xplane.step_account` makes of it on a row. Text and arithmetic:
nothing is compiled and no kernel runs."""

import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import roofline, telemetry, xplane
from paddle_tpu.ops import (hybrid_ops, kernel_cost, pallas_attention,
                            pallas_conv, pallas_conv1d, pallas_kda,
                            pallas_pair_sum, pallas_scan)

S = jax.ShapeDtypeStruct
BF16, F32 = jnp.bfloat16, jnp.float32


def declared(fn, *avals):
    """[(kernel name, FLOPs, transcendentals, bytes)] of the pallas_calls
    a traced `fn` holds."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                cost = eqn.params["cost_estimate"]
                assert cost is not None, eqn.params["name"]
                found.append((eqn.params["name"], cost.flops,
                              cost.transcendentals, cost.bytes_accessed))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*avals).jaxpr)
    return found


def required(op, ins, attrs=None):
    """roofline.op_cost's REQUIRED FLOPs of `op` over avals by slot."""
    return roofline.op_cost(op, {k: [v] for k, v in ins.items()}, {},
                            attrs or {})[0]


# --- the flash kernels: [1, 1024, 2, 128] bf16, tiles of 512, grain 128 ------
# Two Q tiles against two K tiles. Causal: tile (0, 0) and (1, 1) are
# crossed by the diagonal, (1, 0) is open, (0, 1) is dead. A crossed tile
# is 4 x 4 sub-tiles of 128 rows of which the 10 on and under the diagonal
# are computed (6 open, 4 held).
T, H, D, TILE, GRAIN = 1024, 2, 128, 512, 128
Q = S((1, T, H, D), BF16)
STAT = S((1, H, T), F32)
TILE_PAIRS = 3 * TILE * TILE
CAUSAL_PAIRS = TILE * TILE + 2 * 10 * GRAIN * GRAIN
# under a window of 512 keys the open tile (1, 0) becomes one the far
# edge crosses: the 10 sub-tiles on and over ITS diagonal stay
WINDOW_PAIRS = 3 * 10 * GRAIN * GRAIN
WHOLE = T * H * D * 2               # an operand or a result, bf16
STATS = H * T * 4                   # a row statistic, float32


def _fwd(**statics):
    return lambda q, k, v: pallas_attention._fwd_call(
        q, k, v, 0, 0, D ** -0.5, True, True, at=(0, 0), **statics)


def _bwd(**statics):
    return lambda q, k, v, do, lse, dl: pallas_attention._bwd_call(
        q, k, v, do, lse, dl, 0, 0, D ** -0.5, True, at=(0, 0), **statics)


@pytest.mark.parametrize("statics,pairs", [({}, CAUSAL_PAIRS),
                                           ({"window": 512}, WINDOW_PAIRS)],
                         ids=["causal", "window"])
def test_flash_forward_declares_its_live_sub_tiles(statics, pairs):
    (call,) = declared(_fwd(**statics), Q, Q, Q)
    # the scores once a walked tile over the 128 lanes, the second product
    # over the computed pairs alone, D rows deep; one exp a computed score
    assert call == ("flash_fwd", H * (2 * 128 * TILE_PAIRS + 2 * D * pairs),
                    H * pairs,
                    # K and V are one resident major tile: once a head
                    4 * WHOLE + STATS)
    live = required("scaled_dot_product_attention", {"Q": Q, "K": Q},
                    dict(causal=True, **statics))
    assert live == 4 * H * D * sum(
        min(q + 1, statics.get("window", T)) for q in range(T))
    assert call[1] >= live
    # a replayed op handed its kept outputs runs nothing and requires none
    assert required("scaled_dot_product_attention",
                    {"Q": Q, "K": Q, "KeptOut": Q}, {"causal": True}) == 0


def test_block_diffusion_attention_requires_its_kept_pairs():
    # two streams of 8 positions in blocks of 4: every query sees its
    # block's 4 keys and the blocks before it, 4 or 8 keys
    q = S((2, 8, H, D), BF16)
    assert required("block_diffusion_attention", {"Q": q, "K": q},
                    {"block_length": 4}) == 4 * 2 * H * D * (4 * 4 + 4 * 8)


def test_flash_forward_fetches_k_and_v_once_a_live_major_tile():
    """2048 rows in major tiles of 512: Q tile i walks the major tiles
    0..i and the index stays put over the dead ones, so the fetched
    indices read 0000 0111 0122 0123 a head: 9 fetches where whole
    tiles a step would be 16 and once a call 4."""
    q = S((1, 2048, H, D), BF16)
    (call,) = declared(_fwd(major=512), q, q, q)
    major = 512 * D * 2
    assert call[3] == 2 * (2048 * H * D * 2) + H * 2048 * 4 \
        + 2 * H * 9 * major


def test_flash_backward_declares_both_forms():
    avals = (Q, Q, Q, Q, STAT, STAT)
    (fused,) = declared(_bwd(), *avals)
    # four products over the lane block a computed pair, dQ's D rows deep
    assert fused == ("flash_dkv", H * CAUSAL_PAIRS * (8 * 128 + 2 * D),
                     H * CAUSAL_PAIRS, 7 * WHOLE + 2 * STATS)
    dq, dkv = declared(_bwd(fused=False), *avals)
    # scores and dP once a walked tile, dQ over the computed pairs
    assert dq == ("flash_dq", H * (4 * 128 * TILE_PAIRS + 2 * D * CAUSAL_PAIRS),
                  H * CAUSAL_PAIRS, 5 * WHOLE + 2 * STATS)
    assert dkv == ("flash_dkv", H * CAUSAL_PAIRS * 8 * 128, H * CAUSAL_PAIRS,
                   6 * WHOLE + 2 * STATS)
    assert fused[1] + declared(_fwd(), Q, Q, Q)[0][1] >= 3 * required(
        "scaled_dot_product_attention", {"Q": Q, "K": Q}, {"causal": True})


def test_a_shard_with_traced_offsets_counts_its_tiles_whole():
    def ring(q, k, v, off):
        return pallas_attention._fwd_call(q, k, v, off, 0, D ** -0.5, True,
                                          False)
    (call,) = declared(ring, Q, Q, Q, S((), jnp.int32))
    assert call[1] == H * (2 * 128 + 2 * D) * TILE_PAIRS
    assert call[2] == H * TILE_PAIRS


# --- the delta rule: [1, 128, 2 heads of 128 | 128], chunks of 64 ------------
C, K, V, HEADS, CHUNKS = 64, 128, 128, 2, 2
KDA = dict(heads=HEADS, chunk=C, r=2, eps=1e-6, dtype=jnp.dtype(BF16),
           interpret=True)
ROWS = S((1, CHUNKS * C, HEADS * K), BF16)
BETA = S((1, 1, CHUNKS * C, HEADS), F32)
ENTERING = S((1, CHUNKS, HEADS, V, K), F32)
INVERSE = S((1, CHUNKS, 1, C, 2 * C), F32)        # two heads a pack
# a chunk of the pack of two heads (kernel_cost.passes: 6 for `_full`)
SQUARE = 2 * C * (2 * C) ** 2
SCORES = 4 * C * C * 4 * K
FED = 2 * C * 2 * C * 2 * (K + V)
STATE = 2 * C * K * V
WITHIN = 2 * C * 2 * C * 2 * V
RUNNING = 6 * 2 * C * C * HEADS * K
LEVELS = 4                                         # of 6: the first two are
#                                                    written out on the VPU


def _kda_bytes(extra):
    rows = CHUNKS * C * HEADS * K * 2
    return 4 * rows + CHUNKS * C * HEADS * 4 + 2 * HEADS * K * 4 + extra


def test_delta_rule_declares_a_chunk_count():
    operands = (ROWS, ROWS, ROWS, ROWS, BETA, S((HEADS,), F32),
                S((HEADS * K,), F32))
    states = ENTERING.size * 4
    inverses = INVERSE.size * 4
    rows = ROWS.size * 2
    (fwd,) = declared(lambda *a: pallas_kda._forward(*a, **KDA), *operands)
    assert fwd == (
        "kda_scan_fwd",
        CHUNKS * (SCORES + LEVELS * 2 * 6 * SQUARE + FED + 3 * 2 * STATE
                  + WITHIN + RUNNING),
        CHUNKS * (5 + C // 16) * C * HEADS * K,
        _kda_bytes(rows + states + inverses))
    (given,) = declared(lambda *a: pallas_kda._forward(*a, **KDA),
                        *operands, INVERSE)
    # the same call less the inverse's products; the inverses are read
    # where they were written
    assert given[0] == "kda_scan_fwd_given"
    assert fwd[1] - given[1] == CHUNKS * LEVELS * 2 * 6 * SQUARE
    assert given[3] == fwd[3]
    (bwd,) = declared(lambda *a: pallas_kda._backward(*a, **KDA),
                      *operands, ROWS, ENTERING, INVERSE)
    assert bwd == (
        "kda_scan_bwd",
        CHUNKS * (3 * SCORES + 3 * FED + 7 * 2 * STATE + 2 * WITHIN
                  + 2 * 6 * SQUARE + 2 * RUNNING),
        CHUNKS * (6 + C // 16) * C * HEADS * K,
        # reads the forward's operands, Out's cotangent, both residuals;
        # writes five gradients and a's and dt_bias' rows
        _kda_bytes(rows + states + inverses) + 4 * rows
        + CHUNKS * C * HEADS * 4 + 2 * HEADS * K * 4)
    need = required("kda_scan", {"K": S((1, CHUNKS * C, HEADS, K), BF16),
                                 "V": S((1, CHUNKS * C, HEADS, V), BF16)})
    assert need == 6 * CHUNKS * C * HEADS * K * V
    assert given[1] >= need and bwd[1] >= 2 * need


def test_head_decay_form_declares_no_running_sum():
    q = S((1, CHUNKS * C, 1 * K), BF16)           # one key head under two
    cum = S((1, 1, CHUNKS * C, HEADS), F32)
    (fwd,) = declared(
        lambda *a: pallas_kda._forward_a_head(*a, ratio=2, **KDA),
        q, q, ROWS, cum, BETA)
    assert fwd[0] == "gdn_scan_fwd"
    assert fwd[1] == CHUNKS * (SCORES + LEVELS * 2 * 6 * SQUARE + FED
                               + 3 * 2 * STATE + WITHIN)
    assert fwd[2] == CHUNKS * (3 + C // 16) * C * HEADS


# --- the scan: [1, 256, 2 heads of 64], one group, state 128, chunks of 128 --
def test_scan_declares_its_products():
    l, n, rp, r, steps = 128, 128, 128, 2, 2
    cum = S((1, 1, r, steps * l), F32)
    x = S((1, rp, steps * l), BF16)
    b, ct = S((1, steps * l, n), BF16), S((1, n, steps * l), BF16)
    form = dict(chunk=l, r=r, p=64, groups=1, interpret=True)
    (fwd,) = declared(lambda *a: pallas_scan._forward(*a, **form),
                      cum, cum, x, b, ct)
    whole = x.size * 2
    assert fwd == (
        "ssd_scan_fwd",
        steps * (2 * l * l * n + 2 * rp * l * l + 2 * 2 * rp * n * l),
        steps * (r * l * l + 2 * r * l + r * 128),
        # cum, dt, x, B, C^T in; y (float32) and the entering states out
        2 * cum.size * 4 + 3 * whole + whole * 2 + steps * rp * n * 2)
    entering = S((1, steps, rp, n), BF16)
    (bwd,) = declared(lambda *a: pallas_scan._backward(*a, **form),
                      cum, cum, x, x, b, ct, b, ct, entering)
    assert bwd[:2] == ("ssd_scan_bwd", steps * (
        2 * 2 * l * l * n + 3 * 2 * rp * l * l + 5 * 2 * rp * n * l
        + 2 * 2 * n * l * l))
    need = required("ssd_scan", {"X": S((1, steps * l, r, 64), BF16),
                                 "B": S((1, steps * l, 1, n), BF16)},
                    {"chunk_size": l})
    assert fwd[1] >= need and bwd[1] >= 2 * need


# --- the short convolution: [1, 256, 256], four taps and a bias --------------
def test_short_convolution_declares_its_bytes():
    t = c = 256
    k, x = 4, S((1, t, c), BF16)
    taps = S((k + 1, c), F32)
    form = (0, 1, t, c, k, True, jnp.dtype(BF16), None, None, True)
    (fwd,) = declared(pallas_conv1d._fwd_call(*form), x, taps)
    assert fwd == ("causal_conv1d_fwd", t * c * (2 * k + 4), t * c,
                   2 * t * c * 2 + taps.size * 4)
    (bwd,) = declared(pallas_conv1d._bwd_call(*form), x, x, x, taps)
    fold = bwd[3] - (3 * t * c * 2 + taps.size * 4)
    # X and Out's cotangent in, dX out, the taps; the steps before the
    # block and the taps' partial sums are the rest, under a tenth
    assert bwd[:3] == ("causal_conv1d_bwd", t * c * (3 * 2 * k + 1 + 8),
                       t * c)
    assert 0 < fold < 0.2 * bwd[3]
    need = required("causal_conv1d", {"X": x, "Filter": S((c, k), F32)})
    assert fwd[1] >= need
    assert bwd[1] >= 2 * need


# --- the token side, the int8 convolution, the empty call --------------------
def test_pair_sum_declares_what_every_run_does():
    c, n, k, d, held = 256, 256, 2, 128, 2
    tile, window = pallas_pair_sum._TILE, pallas_pair_sum._WINDOW
    lanes, tiles = 128, n // tile                   # 2 x 16 lanes in a block
    call = pallas_pair_sum._call(c, n, k, d, held, jnp.dtype(BF16), True,
                                 jnp.dtype(F32), tile, window, True)
    index = S((tiles * held,), jnp.int32)
    (got,) = declared(call, index, index, S((1,), jnp.int32),
                      S((tiles, 3, lanes), jnp.int32), S((n, k), jnp.int32),
                      S((n, k), F32), S((c, d), BF16))
    # one round a tile at least, the weighted bf16 form's three products;
    # the rows its own copies fetch are the routing's and are left out
    assert got == ("pair_sum", tiles * 3 * 2 * tile * lanes * d, 0,
                   tiles * 3 * lanes * 4 + 2 * n * k * 4 + n * d * 4)


def test_int8_convolution_counts_half_a_pass():
    x, w = S((1, 8, 8, 128), jnp.int8), S((3, 3, 128, 128), jnp.int8)
    (got,) = declared(
        lambda x, w, dq: pallas_conv._conv_call(
            x, w, (1, 1), (1, 1), (1, 1), dq, BF16), x, w, S((1, 128), F32))
    steps = 3 * 8                   # KH taps x the H block's 8 rows
    assert got == ("conv2d_q8", steps * 3 * 8 * 128 * 128, 0,
                   # a padded input row a step, a filter tap once an H
                   # block, the scales once, the result once
                   steps * 10 * 128 + 3 * 3 * 128 * 128 + 128 * 4
                   + 8 * 8 * 128 * 2)


def test_unwritten_rows_declare_nothing():
    (got,) = declared(
        lambda rows: hybrid_ops._over_all_pairs(rows, 64, True),
        S((16, 128), BF16))
    assert got == ("unwritten_rows", 0, 0, 0)


def test_every_pallas_call_of_the_package_declares():
    root = os.path.dirname(pallas_attention.__file__)
    calls = declarations = 0
    for name in os.listdir(root):
        if name.endswith(".py"):
            with open(os.path.join(root, name)) as f:
                text = f.read()
            calls += text.count("pl.pallas_call(")
            declarations += text.count("cost_estimate=")
    assert calls == declarations == 8


def test_fetches_walks_the_grid_as_the_pipeline_does():
    # an index that holds still is one fetch; one that follows the last
    # axis is a fetch a step; a long leading axis repeats its walk
    assert kernel_cost.fetches((4, 3), lambda i, j: (0, 0)) == 1
    assert kernel_cost.fetches((4, 3), lambda i, j: (i, 0)) == 4
    assert kernel_cost.fetches((4, 3), lambda i, j: (i, j)) == 12
    assert kernel_cost.fetches((5, 2, 3), lambda b, i, j: (b, min(j, i))) \
        == 5 * 2      # a row: 000 011
    assert kernel_cost.fetches((2, 2), lambda i, j, off: (i + off[0], 0),
                               (7,)) == 2
    assert kernel_cost.passes(F32, highest=True) == 6
    assert kernel_cost.passes(F32) == kernel_cost.passes(BF16, True) == 1


# --- the parse ---------------------------------------------------------------
BODY = "QUJD" * 4000        # a serialized kernel: most of the line
CALL = ('  %%%s = bf16[8,128,512]{2,1,0:T(8,128)(2,1)} custom-call(%%x), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(fn)/pd_at.4/pd_role.forward/pd.%s/%s/pallas_call"}%s\n')
CONFIG = (', backend_config={"custom_call_config":{"body":"' + BODY + '",'
          '"cost_estimate":{"flops":"%d","transcendentals":"%d",'
          '"bytes_accessed":"%d","remote_bytes_transferred":"0"},'
          '"needs_layout_passes":true},"used_scoped_memory_configs":[]}')
OLD_CONFIG = (', backend_config={"custom_call_config":{"body":"' + BODY
              + '","needs_layout_passes":true}}')
MODULE = (
    "HloModule jit_fn, is_scheduled=true\n\n"
    "ENTRY %main (x: bf16[8,128,512]) -> bf16[8,128,512] {\n"
    "  %x = bf16[8,128,512]{2,1,0:T(8,128)(2,1)} parameter(0)\n"
    "  %y = bf16[8,128,512]{2,1,0:T(8,128)(2,1)S(1)} copy(%x)\n"
    + CALL % ("flash_fwd.1", "scaled_dot_product_attention", "flash_fwd",
              CONFIG % (394000000, 1000, 8190000))
    + CALL % ("gmm.1", "moe_experts", "gmm", CONFIG % (5, 0, 7))
    + CALL % ("quiet.1", "moe_experts", "pair_sum", OLD_CONFIG)
    + (CALL % ("held.1", "causal_conv1d", "causal_conv1d_fwd",
               CONFIG % (0, 0, 9000))).replace("(%x)", "(%x, %y)")
    + "  ROOT %add.1 = bf16[8,128,512]{2,1,0:T(8,128)(2,1)} add(%flash_fwd.1,"
      " %gmm.1)\n}\n")


def test_parse_reads_a_mosaic_calls_declaration():
    by_name = {i.name: i for i in xplane.hlo_instructions(MODULE)}
    flash, gmm, quiet = (by_name[n] for n in ("flash_fwd.1", "gmm.1",
                                              "quiet.1"))
    assert (flash.declared_flops, flash.declared_transcendentals,
            flash.declared_bytes, flash.declared_by) == (
                394e6, 1000.0, 8190000, "kernel")
    assert (gmm.declared_flops, gmm.declared_bytes, gmm.declared_by) == (
        5.0, 7, "jax")
    # compiled before the kernels declared anything, or served by a cache
    # from then: nothing to read and nothing fails
    assert (quiet.declared_flops, quiet.declared_transcendentals,
            quiet.declared_bytes, quiet.declared_by) == (None,) * 4
    for call in (flash, gmm, quiet):
        # what the benchmark's readers tell a Mosaic call by
        assert call.flops is None and call.mxu_flops is None
        assert xplane.floor_seconds(call, 197e12, 819e9) is None
        assert call.op in ("scaled_dot_product_attention", "moe_experts")
    assert xplane.kernel_floor_seconds(quiet, 197e12, 819e9) is None
    assert xplane.kernel_floor_seconds(flash, 197e12, 819e9) == (
        pytest.approx(8.19e6 / 819e9), "bytes")
    assert by_name["add.1"].declared_by is None
    # one of the call's three arrays is held in VMEM (`S(1)`: a copy
    # brought it there): its share of the declared bytes is no HBM traffic
    assert by_name["held.1"].declared_bytes == 9000 * 2 // 3
    # an account saved and loaded keeps the declaration; one saved by an
    # older tree (no such fields) loads with none
    assert xplane.Instr(**{f: getattr(flash, f) for f in xplane.Instr._fields
                           if not f.startswith("declared")}).declared_by \
        is None


# --- the rows ----------------------------------------------------------------
def _trace(tmp_path, names_ms):
    """A one-chip trace of one step that ran `names_ms` in turn."""
    from jax.profiler import ProfileData
    events, metadata, at = [], [], 0
    for key, (name, ms) in enumerate(names_ms, 1):
        ps = int(ms * 1e9)
        events.append("events { metadata_id: %d offset_ps: %d duration_ps: "
                      "%d }" % (key, at, ps))
        metadata.append('event_metadata { key: %d value { id: %d name: '
                        '"%%%s = bf16[8] custom-call()" } }' % (key, key, name))
        at += ps
    text = """planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 90 offset_ps: 0 duration_ps: %d } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 1000 %s }
      %s
      event_metadata { key: 90 value { id: 90 name: "jit_fn(1)" } } }
    """ % (at, " ".join(events), " ".join(metadata))
    out = tmp_path / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_rows_carry_the_kernels_floor_beside_the_accounts(tmp_path,
                                                          monkeypatch):
    # whatever accounts earlier tests of this process left are not this
    # trace's: the join reads the one saved beside it, with its chip
    monkeypatch.setattr(xplane, "_ACCOUNTS", type(xplane._ACCOUNTS)())
    trace = _trace(tmp_path, [("flash_fwd.1", 0.5), ("flash_fwd.1", 0.5),
                              ("gmm.1", 0.2), ("quiet.1", 0.1),
                              ("add.1", 0.1)])
    instrs = xplane.compact(xplane.hlo_instructions(MODULE))
    xplane._save_accounts(trace, [instrs], "TPU v5 lite")
    account = xplane.step_account(trace, accounts=None)
    rows = {r["name"]: r for r in account["steps"][0]["rows"]}
    flash = rows["flash_fwd.1"]
    # two runs of a call whose floor is its bytes, 0.01 ms each
    assert flash["count"] == 2 and flash["ms"] == pytest.approx(1.0)
    assert flash["kernel_floor_ms"] == pytest.approx(2 * 0.01)
    assert flash["kernel_bound"] == "bytes"
    assert flash["declared_by"] == "kernel"
    assert flash["declared_flops"] == 394e6
    assert rows["gmm.1"]["declared_by"] == "jax"
    assert rows["gmm.1"]["kernel_floor_ms"] == pytest.approx(
        1e3 * 7 / 819e9)
    for name in ("flash_fwd.1", "gmm.1", "quiet.1"):
        assert rows[name]["flops"] is None
        assert rows[name]["floor_ms"] is None and rows[name]["bound"] is None
    assert rows["quiet.1"]["kernel_floor_ms"] is None
    assert rows["quiet.1"]["declared_by"] is None
    assert rows["add.1"]["kernel_floor_ms"] is None
    assert rows["add.1"]["floor_ms"] is not None
    # and the operator's report: executed beside required, the kernels
    # (handed its accounts the join asks this process for its chip)
    monkeypatch.setattr(xplane, "_peaks",
                        lambda kind: (197e12, 819e9, "TPU v5 lite"))
    report = roofline.collect_report(trace, probe=False, accounts=[
        (instrs, {"cost": lambda: {
            "ops": {"scaled_dot_product_attention": {
                "flops": 197e6, "bytes": 1.0}},
            "total_flops": 197e6, "total_bytes": 1.0}})])
    (op,) = [o for o in report["kernel_ops"]
             if o["op"] == "scaled_dot_product_attention"]
    assert op["executed_flops"] == pytest.approx(2 * 394e6)
    assert op["required_flops"] == 197e6 and op["added"] == pytest.approx(4)
    kernels = {k["kernel"]: k for k in report["kernels"]}
    assert kernels["flash_fwd"]["calls"] == 2
    assert kernels["flash_fwd"]["share"] == pytest.approx(0.02)
    assert kernels["gmm"]["declared_by"] == "jax"
    assert "pair_sum" not in kernels          # declared nothing
    text = "\n".join(roofline.format_report(report))
    assert "[work] scaled_dot_product_attention" in text
    assert "[mosaic] flash_fwd" in text
    # read again from the directory alone: the required side was left there
    later = roofline.collect_report(trace, probe=False)
    assert [o["required_flops"] for o in later["kernel_ops"]
            if o["op"] == "scaled_dot_product_attention"] == [197e6]


# --- the persistent cache's answers ------------------------------------------
def test_compile_cache_answers_are_booked_by_program():
    telemetry.reset()
    import jax.monitoring
    with telemetry.watch_build("p7"):
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    booked = telemetry.snapshot()["counters"]["executor_compile_cache_total"]
    assert booked == {"program=p7,result=hit": 2, "program=p7,result=miss": 1,
                      "program=-,result=miss": 1}
    table = roofline._build_table(telemetry.snapshot())
    assert table["p7"]["cache"] == {"hit": 2, "miss": 1}
    line = roofline.format_report(
        {"rows": [], "build": table, "notes": []})[-2:]
    assert any("cache: 2 loaded, 1 compiled" in ln for ln in line)
