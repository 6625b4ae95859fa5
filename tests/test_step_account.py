"""The one parse of a compiled step (xplane.hlo_instructions), the one
reader of a trace (xplane.device_steps), their join (xplane.step_account)
and the account the executor keeps of each compiled block (ISSUE 35).
Hand-written HLO text and a hand-written trace whose numbers can be
checked by hand; then the tiny GPT-2 and ResNet cells compiled for the
CPU, held to XLA's own count of their steps."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import flags, telemetry, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "benchmark", "data")
MESH = {"fsdp": 2, "tp": 2}


def _entry(body, header=""):
    return "HloModule m, is_scheduled=true\n\n%s\nENTRY %%main () -> f32[] " \
        "{\n%s\n}\n" % (header, body)


def _only(text, name, mesh=None):
    (got,) = [i for i in xplane.hlo_instructions(text, mesh=mesh)
              if i.name == name]
    return got


# --- products ---------------------------------------------------------------

# (name, body, FLOPs): each by hand. A window position counts only where
# it reads an input element, as XLA's cost analysis has it: a 3-wide
# window with padding 1 over 8 reads 7 + 8 + 7 = 22 (position, tap) pairs.
PRODUCTS = [
    ("dot", """\
  %a = f32[16,32]{1,0} parameter(0)
  %b = f32[32,8]{1,0} parameter(1)
  ROOT %dot.1 = f32[16,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}""",
     2 * 16 * 8 * 32),
    ("dot with a batch dimension", """\
  %a = bf16[4,16,32]{2,1,0} parameter(0)
  %b = bf16[4,32,8]{2,1,0} parameter(1)
  ROOT %dot.1 = bf16[4,16,8]{2,1,0} dot(%a, %b), lhs_batch_dims={0}, lhs_contracting_dims={2}, rhs_batch_dims={0}, rhs_contracting_dims={1}""",
     2 * 4 * 16 * 8 * 32),
    ("conv forward", """\
  %a = bf16[2,8,8,4]{3,2,1,0} parameter(0)
  %b = bf16[3,3,4,8]{3,2,1,0} parameter(1)
  ROOT %dot.1 = bf16[2,8,8,8]{3,2,1,0} convolution(%a, %b), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f""",
     2 * 2 * 4 * 8 * 22 * 22),
    ("conv forward, two feature groups", """\
  %a = bf16[2,8,8,4]{3,2,1,0} parameter(0)
  %b = bf16[3,3,2,8]{3,2,1,0} parameter(1)
  ROOT %dot.1 = bf16[2,8,8,8]{3,2,1,0} convolution(%a, %b), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, feature_group_count=2""",
     2 * 2 * 2 * 8 * 22 * 22),
    # the gradient of a stride-2 conv to its input: the output gradient
    # dilated by 2 (reach 7), padded 1 and 2; of 3 x 8 (tap, position)
    # pairs 4 + 4 + 3 = 11 read an element
    ("conv grad-input of stride 2", """\
  %a = bf16[2,4,4,8]{3,2,1,0} parameter(0)
  %b = bf16[3,3,8,4]{3,2,1,0} parameter(1)
  ROOT %dot.1 = bf16[2,8,8,4]{3,2,1,0} convolution(%a, %b), window={size=3x3 pad=1_2x1_2 lhs_dilate=2x2 rhs_reversal=1x1}, dim_labels=b01f_01io->b01f""",
     2 * 2 * 8 * 4 * 11 * 11),
    # the gradient to the filter: the batch is the contracted feature,
    # the output gradient the 8 x 8 "window": as many multiply-adds as
    # the forward conv
    ("conv grad-filter", """\
  %a = bf16[2,8,8,4]{3,2,1,0} parameter(0)
  %b = bf16[2,8,8,8]{3,2,1,0} parameter(1)
  ROOT %dot.1 = bf16[3,3,4,8]{3,2,1,0} convolution(%a, %b), window={size=8x8 pad=1_1x1_1}, dim_labels=f01b_i01o->01bf""",
     2 * 2 * 4 * 8 * 22 * 22),
    ("conv grad-filter, two batch groups", """\
  %a = bf16[2,8,8,4]{3,2,1,0} parameter(0)
  %b = bf16[2,8,8,8]{3,2,1,0} parameter(1)
  ROOT %dot.1 = bf16[3,3,2,8]{3,2,1,0} convolution(%a, %b), window={size=8x8 pad=1_1x1_1}, dim_labels=f01b_i01o->01bf, batch_group_count=2""",
     2 * 2 * 2 * 8 * 22 * 22),
]


@pytest.mark.parametrize("name,body,flops", PRODUCTS,
                         ids=[p[0] for p in PRODUCTS])
def test_product_flops_alone_and_inside_a_fusion(name, body, flops):
    alone = _only(_entry(body), "dot.1")
    assert alone.flops == flops
    assert alone.heavy == ("dot" if name.startswith("dot") else "convolution")
    a, b = (ln.split(" = ")[1].split(" parameter")[0]
            for ln in body.splitlines()[:2])
    out = body.splitlines()[2].split(" = ")[1].split(" ")[0]
    assert alone.bytes == sum(xplane.shape_bytes(s) for s in (a, b, out))
    # the same product as the body of a fusion that also adds to it
    fused = ("%%fused (p0: x, p1: x) -> x {\n%s\n}\n" % body) \
        .replace("ROOT %dot.1", "%dot.1").replace(
            "\n}", "\n  ROOT %%add.1 = %s add(%%dot.1, %%dot.1)\n}" % out)
    text = _entry("  %%x = %s parameter(0)\n  %%y = %s parameter(1)\n"
                  "  ROOT %%fusion.1 = %s fusion(%%x, %%y), kind=kOutput, "
                  "calls=%%fused" % (a, b, out), fused)
    wrapped = _only(text, "fusion.1")
    assert wrapped.heavy == alone.heavy
    assert wrapped.flops == flops + xplane.first_array(out)[0]
    assert wrapped.bytes == alone.bytes
    assert wrapped.detail.startswith(xplane._plain(a) + " * ")


def test_float32_product_at_highest_precision_runs_six_passes():
    """`flops` is the model's count (XLA's cost analysis, `mfu_pct`'s
    convention); `mxu_flops`, what the floor is reckoned from, counts a
    float32 product at `highest` precision as the six bf16 passes the MXU
    runs it as: the `flops` stat of a v5e trace's event (PR 35, call 2:
    the router's f32[4096,2688] x [2688,128] read 16.9 GFLOP, 6 x 2.82)."""
    body = """\
  %a = f32[4096,2688]{1,0} parameter(0)
  %b = f32[2688,128]{1,0} parameter(1)
  ROOT %dot.1 = f32[4096,128]{1,0} convolution(%a, %b), dim_labels=bf_io->bf, operand_precision={highest,highest}"""
    got = _only(_entry(body), "dot.1")
    assert got.flops == 2 * 4096 * 2688 * 128 == 2818572288
    assert got.mxu_flops == 6 * got.flops
    plain = _only(_entry(body.replace(
        ", operand_precision={highest,highest}", "")), "dot.1")
    assert plain.mxu_flops == plain.flops == got.flops
    half = _only(_entry(body.replace("f32[", "bf16[")), "dot.1")
    assert half.mxu_flops == half.flops     # bf16 operands: one pass


def test_a_scans_stacked_output_beside_a_product_costs_the_update():
    """A loop body's fusion that gives a product AND writes one step's
    row of the scan's stacked output in place (a tuple root, one result a
    dynamic-update-slice of an operand): the stacked array costs the
    update, not its 32 rows read and written (PR 55: the delta rule's
    walk over the chunks read 108 % of its roofline with the whole array
    charged to every step)."""
    fused = """\
%fused (p0: x, p1: x, p2: x, p3: x) -> x {
  %p0 = bf16[32,64,128]{2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %p2 = bf16[64,128]{1,0} parameter(2)
  %p3 = bf16[128,128]{1,0} parameter(3)
  %zero = s32[] constant(0)
  %dot.1 = f32[64,128]{1,0} dot(%p2, %p3), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %half = bf16[64,128]{1,0} convert(%dot.1)
  %row = bf16[1,64,128]{2,1,0} bitcast(%half)
  %dus = bf16[32,64,128]{2,1,0} dynamic-update-slice(%p0, %row, %p1, %zero, %zero)
  ROOT %both = (bf16[32,64,128]{2,1,0}, f32[64,128]{1,0}) tuple(%dus, %dot.1)
}
"""
    text = _entry("""\
  %stack = bf16[32,64,128]{2,1,0} parameter(0)
  %i = s32[] parameter(1)
  %a = bf16[64,128]{1,0} parameter(2)
  %b = bf16[128,128]{1,0} parameter(3)
  ROOT %fusion.1 = (bf16[32,64,128]{2,1,0}, f32[64,128]{1,0}) fusion(%stack, %i, %a, %b), kind=kOutput, calls=%fused""", fused)
    got = _only(text, "fusion.1")
    assert got.heavy == "dot"
    row, product = 2 * 64 * 128, 4 * 64 * 128
    assert got.bytes == 4 + 2 * 64 * 128 + 2 * 128 * 128 + row + product


def test_an_operand_sliced_inside_a_nested_fusion_costs_the_slice():
    """A loop body's product whose operand fusion picks one step's row of
    a scan's stacked input: the fusion reads the row, wherever inside it
    the slice is taken; an operand a nested fusion reads whole is
    charged whole."""
    fused = """\
%pick (q0: x, q1: x) -> x {
  %q0 = bf16[32,128,128]{2,1,0} parameter(0)
  %q1 = s32[] parameter(1)
  %zero = s32[] constant(0)
  %ds = bf16[1,128,128]{2,1,0} dynamic-slice(%q0, %q1, %zero, %zero), dynamic_slice_sizes={1,128,128}
  ROOT %one = bf16[128,128]{1,0} bitcast(%ds)
}

%whole (r0: x) -> x {
  %r0 = bf16[32,128,128]{2,1,0} parameter(0)
  %c = bf16[] constant(0)
  ROOT %sum = bf16[128,128]{1,0} reduce(%r0, %c), dimensions={0}, to_apply=%region
}

%fused (p0: x, p1: x, p2: x) -> x {
  %p0 = bf16[32,128,128]{2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %p2 = bf16[64,128]{1,0} parameter(2)
  %row = bf16[128,128]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%PICK
  ROOT %dot.1 = f32[64,128]{1,0} dot(%p2, %row), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    body = """\
  %stack = bf16[32,128,128]{2,1,0} parameter(0)
  %i = s32[] parameter(1)
  %a = bf16[64,128]{1,0} parameter(2)
  ROOT %fusion.1 = f32[64,128]{1,0} fusion(%stack, %i, %a), kind=kOutput, calls=%fused"""
    rest = 4 + 2 * 64 * 128 + 4 * 64 * 128
    got = _only(_entry(body, fused.replace("%PICK", "%pick")), "fusion.1")
    assert got.bytes == 2 * 128 * 128 + rest
    got = _only(_entry(body, fused.replace("%PICK", "%whole").replace(
        "fusion(%p0, %p1), kind", "fusion(%p0), kind")), "fusion.1")
    assert got.bytes == 32 * 2 * 128 * 128 + rest


def test_mosaic_call_elementwise_reduce_and_copies():
    text = _entry("""\
  %p = bf16[4,128,256]{2,1,0:T(8,128)(2,1)} parameter(0)
  %flash_fwd.3 = bf16[4,128,256]{2,1,0:T(8,128)(2,1)} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/pd_at.12/pd_role.forward/pd_scope.latent_attention/pd.scaled_dot_product_attention/jit(flash_fwd)/flash_fwd/pallas_call"}
  %exp.1 = bf16[4,128,256]{2,1,0:T(8,128)(2,1)} exponential(%flash_fwd.3)
  %mul.1 = bf16[4,128,256]{2,1,0:T(8,128)(2,1)} multiply(%exp.1, %p), metadata={op_name="jit(fn)/pd_at.13/pd_role.backward/pd.gelu_grad/mul"}
  %c = f32[] constant(0)
  %reduce.1 = bf16[4,128]{1,0} reduce(%mul.1, %c), dimensions={2}, to_apply=%region
  %transpose.1 = bf16[4,256,128]{2,1,0} transpose(%mul.1), dimensions={0,2,1}
  %slice-start.1 = ((bf16[4,256,128]{2,1,0}), bf16[1,256,128]{2,1,0:S(1)}, s32[]{:S(2)}) slice-start(%transpose.1), slice={[0:1], [0:256], [0:128]}
  %slice-done.1 = bf16[1,256,128]{2,1,0:S(1)} slice-done(%slice-start.1)
  ROOT %t = (bf16[4,128]{1,0}, bf16[1,256,128]{2,1,0:S(1)}) tuple(%reduce.1, %slice-done.1)""",
                  "%region (a: f32[], b: f32[]) -> f32[] {\n  %a = f32[] "
                  "parameter(0)\n  %b = f32[] parameter(1)\n  ROOT %s = "
                  "f32[] add(%a, %b)\n}\n")
    got = {i.name: i for i in xplane.hlo_instructions(text)}
    elements = 4 * 128 * 256
    kernel = got["flash_fwd.3"]     # named by its pallas_call, not jit()
    assert kernel.heavy == "flash_fwd" and kernel.flops is None
    assert kernel.mxu_flops is None
    assert (kernel.role, kernel.scope, kernel.op, kernel.at) == (
        "forward", "latent_attention", "scaled_dot_product_attention", 12)
    assert got["exp.1"].flops == 0.0        # a transcendental, not a FLOP
    assert got["exp.1"].heavy == "elementwise"
    assert got["mul.1"].flops == elements
    assert (got["mul.1"].role, got["mul.1"].op, got["mul.1"].at) == (
        "backward", "gelu_grad", 13)
    assert got["reduce.1"].heavy == "reduce"
    assert got["reduce.1"].flops == elements - 4 * 128
    assert got["transpose.1"].heavy == "copy"
    assert got["transpose.1"].bytes == 2 * elements * 2
    # an async slice moves its output, not the operand it names first
    assert got["slice-start.1"].bytes == 2 * 256 * 128 * 2
    assert got["slice-done.1"].bytes == 0
    assert xplane.is_async("slice-done") and not xplane.is_async("slice")
    # what a compiled block keeps: nothing that takes no time
    assert [i.name for i in xplane.compact(list(got.values()))] == [
        "flash_fwd.3", "exp.1", "mul.1", "reduce.1", "transpose.1",
        "slice-start.1", "slice-done.1"]


def test_async_halves_in_their_plain_spelling_read_as_the_sugared_ones():
    """A chip's text spells an async slice `async-start(...), calls=%c` /
    `async-done` where a described compile prints `slice-start` /
    `slice-done`: one rule reads both."""
    text = """HloModule m

%async_computation (p: f32[8,8]) -> f32[2,8] {
  %p = f32[8,8]{1,0} parameter(0)
  ROOT %slice.1 = f32[2,8]{1,0:S(1)} slice(%p), slice={[0:2], [0:8]}
}

ENTRY %main (x: f32[8,8]) -> f32[2,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %async-start.3 = ((f32[8,8]{1,0}), f32[2,8]{1,0:S(1)}, s32[]{:S(2)}) async-start(%x), calls=%async_computation
  ROOT %async-done.3 = f32[2,8]{1,0:S(1)} async-done(%async-start.3)
}
"""
    start, done = xplane.compact(xplane.hlo_instructions(text))
    assert (start.name, start.opcode, start.heavy) == (
        "async-start.3", "slice-start", "copy")
    assert start.bytes == 2 * 2 * 8 * 4         # the slice, read and written
    assert (done.opcode, done.heavy, done.bytes) == ("slice-done", "copy", 0)
    assert xplane.is_async(start.opcode) and xplane.is_async(done.opcode)


def test_provenance_rules():
    assert xplane.provenance(
        "jit(fn)/pd_at.17/pd_role.backward/pd_scope.mtp_block."
        "latent_attention/pd.fused_chain/pd_role.backward/pd.mul_grad/"
        "transpose(jvp())/dot_general") == (
        "backward", "mtp_block.latent_attention", "fused_chain", 17)
    assert xplane.provenance("jit(fn)/pd.mul/dot_general") == (
        "unattributed", None, "mul", None)
    assert xplane.provenance("jit(fn)/jit(remainder)/rem") == (
        "unattributed", None, None, None)
    assert xplane.provenance("") == ("unattributed", None, None, None)
    # the position is spelt so that no older rule takes it for a type
    import re
    assert not re.search(r"(?<![A-Za-z0-9_])pd\.", "pd_at.17")


# --- collectives on a 2 x 2 mesh --------------------------------------------

GROUPS = [
    ("{{0,1},{2,3}}", "tp", 2), ("{{0,2},{1,3}}", "fsdp", 2),
    ("[2,2]<=[4]", "tp", 2), ("[2,2]<=[2,2]T(1,0)", "fsdp", 2),
    ("{{0,1,2,3}}", "fsdp+tp", 4), ("[1,4]<=[4]", "fsdp+tp", 4),
    ("[1,4]<=[2,2]T(1,0)", "fsdp+tp", 4),
]


@pytest.mark.parametrize("groups,axis,size", GROUPS,
                         ids=[g[0] for g in GROUPS])
def test_replica_groups_read_right_on_a_2x2_mesh(groups, axis, size):
    text = _entry("""\
  %%g = f32[256,512]{1,0} parameter(0)
  ROOT %%all-reduce.1 = f32[256,512]{1,0} all-reduce(%%g), channel_id=1, replica_groups=%s, use_global_device_ids=true, to_apply=%%add""" % groups)
    got = _only(text, "all-reduce.1", mesh=MESH)
    assert (got.kind, got.axis, got.group_size) == ("all-reduce", axis, size)
    assert got.payload == 256 * 512 * 4 and got.heavy == "collective"
    # without the planner's mesh there is a group size and no guess
    bare = _only(text, "all-reduce.1")
    assert bare.axis is None and bare.group_size == size


def test_async_pairs_permutes_and_fused_collectives():
    text = _entry("""\
  %g = f32[128,512]{1,0} parameter(0)
  %all-gather-start.1 = (f32[128,512]{1,0}, f32[256,512]{1,0}) all-gather-start(%g), channel_id=2, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, metadata={op_name="jit(fn)/pd_at.9/pd_role.optimize/pd.fused_adam/pd.coll.fsdp_gather/mul"}
  %all-gather-done.1 = f32[256,512]{1,0} all-gather-done(%all-gather-start.1)
  %collective-permute-start.1 = (f32[128,512]{1,0}, f32[128,512]{1,0}, u32[], u32[]) collective-permute-start(%g), channel_id=3, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %collective-permute-done.1 = f32[128,512]{1,0} collective-permute-done(%collective-permute-start.1)
  %fusion.7 = f32[64,512]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter
  ROOT %t = (f32[256,512]{1,0}, f32[128,512]{1,0}, f32[64,512]{1,0}) tuple(%all-gather-done.1, %collective-permute-done.1, %fusion.7)""",
                  "%all-reduce-scatter (p: f32[128,512]) -> f32[64,512] {\n"
                  "  %p = f32[128,512]{1,0} parameter(0)\n"
                  "  %all-reduce.9 = f32[128,512]{1,0} all-reduce(%p), "
                  "replica_groups=[2,2]<=[4], to_apply=%add\n"
                  "  ROOT %ds = f32[64,512]{1,0} dynamic-slice(%all-reduce.9)"
                  ", dynamic_slice_sizes={64,512}\n}\n")
    got = {i.name: i for i in xplane.hlo_instructions(text, mesh=MESH)}
    start, done = got["all-gather-start.1"], got["all-gather-done.1"]
    assert (start.kind, start.axis, start.site) == (
        "all-gather", "fsdp", "fsdp_gather")
    assert start.payload == 256 * 512 * 4 and done.payload == 0
    # the done half names no groups: they are its start's
    assert (done.kind, done.axis, done.group_size) == ("all-gather", "fsdp", 2)
    assert xplane.op_label(start) == "coll.fsdp_gather"
    permute = got["collective-permute-start.1"]
    assert (permute.kind, permute.axis, permute.group_size) == (
        "collective-permute", "tp", 2)
    assert got["collective-permute-done.1"].axis == "tp"
    # a fusion that wraps a collective is one, with the inner one's groups
    fused = got["fusion.7"]
    assert (fused.kind, fused.heavy, fused.axis) == (
        "all-reduce", "collective", "tp")
    # what it hands to the collective is the collective's payload, not
    # HBM traffic of its own: only the slice it writes is
    assert fused.payload == 128 * 512 * 4 and fused.bytes == 64 * 512 * 4


# --- the trace and the join -------------------------------------------------

def _trace(tmp_path):
    from jax.profiler import ProfileData

    tmp_path.mkdir(exist_ok=True)
    with open(os.path.join(DATA, "step_account.pbtxt")) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    out = tmp_path / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def _account():
    with open(os.path.join(DATA, "step_account.hlo.txt")) as f:
        return xplane.compact(xplane.hlo_instructions(f.read(), mesh=MESH))


def test_device_steps_reads_the_ops_line_alone(tmp_path):
    steps = xplane.device_steps(_trace(tmp_path))
    assert [(s["device"], s["module"]) for s in steps] == [
        ("/device:TPU:0", "jit_fn(7)"), ("/device:TPU:1", "jit_fn(7)")]
    first = steps[0]["events"]
    # fusion.1 is also on a derived line of chip 0: counted once
    assert [e[0] for e in first].count("fusion.1") == 1
    assert len(first) == 11 and len(steps[1]["events"]) == 10
    # an event goes by its instruction's name and carries what its
    # metadata's stats say (`tf_op` is the op_name on a v5e)
    product = first[3]
    assert product[0] == "fusion.1" and product[2] == 1_000_000_000
    assert product[3]["tf_op"].endswith("pd_scope.block/pd.mul/dot_general")
    assert product[3]["flops"] == 536870912
    assert product[3]["bytes_accessed"] == 2623488


def test_join_sums_to_busy_and_floors_come_from_the_chips_table(tmp_path):
    account = xplane.step_account(_trace(tmp_path), accounts=[_account()])
    assert account["joined"] == pytest.approx(1.0) and account["used"] == [0]
    assert account["peak_flops"] is None        # read on the CPU: no peaks
    assert all(r["floor_ms"] is None for s in account["steps"]
               for r in s["rows"])
    for step, busy in zip(account["steps"], (4.9, 4.4)):
        assert step["busy_ms"] == pytest.approx(busy)
        assert sum(r["ms"] for r in step["rows"]) == pytest.approx(busy)
    # the same join where the trace says which chip it is from
    xplane._save_accounts(str(tmp_path), [_account()], "TPU v5 lite")
    xplane.forget_accounts()
    account = xplane.step_account(str(tmp_path))
    assert account["peak_flops"] == 197e12
    assert account["hbm_bytes_per_s"] == 819e9
    rows = {r["name"]: r for r in account["steps"][0]["rows"]}
    product = rows["fusion.1"]
    assert (product["role"], product["scope"], product["op"],
            product["at"]) == ("forward", "block", "mul", 3)
    assert product["bound"] == "bytes"
    assert product["floor_ms"] == pytest.approx(1e3 * product["bytes"] / 819e9)
    # the bias it adds was prefetched into VMEM: no HBM bytes of its own
    assert product["bytes"] == (8 * 128 * 256 + 256 * 512 + 8 * 128 * 512) * 2
    assert rows["copy-done.1"]["floor_ms"] is None      # an async half
    assert rows["flash_fwd.1"]["flops"] is None
    reduce = rows["all-reduce.1"]
    assert (reduce["axis"], reduce["group_size"]) == ("tp", 2)
    # 512 KiB in 1 ms, times 2 (n - 1) / n at its own group of two
    assert reduce["busbw_gbps"] == pytest.approx(524288 / 1e-3 / 1e9 * 1.0)
    assert rows["all-reduce.2"]["axis"] == "fsdp+tp"


def test_event_without_an_account_keeps_the_traces_provenance(tmp_path):
    account = xplane.step_account(_trace(tmp_path), accounts=[])
    assert account["joined"] == 0.0
    rows = {r["name"]: r for r in account["steps"][0]["rows"]}
    assert rows["fusion.1"]["joined"] is False
    assert (rows["fusion.1"]["role"], rows["fusion.1"]["op"],
            rows["fusion.1"]["at"]) == ("forward", "mul", 3)
    assert rows["all-reduce.1"]["kind"] == "all-reduce"
    assert rows["all-reduce.1"]["axis"] is None


def test_blocks_that_share_a_module_name_are_told_apart(tmp_path):
    other = xplane.hlo_instructions(_entry(
        "  %p = f32[8]{0} parameter(0)\n  ROOT %fusion.1 = f32[8]{0} "
        "negate(%p)"))
    account = xplane.step_account(_trace(tmp_path),
                                  accounts=[other, _account()])
    assert account["used"] == [1]
    assert account["joined"] == pytest.approx(1.0)
    # of two that name it equally well, the newest: the block compiled last
    twice = xplane.step_account(_trace(tmp_path / "again"),
                                accounts=[_account(), _account()])
    assert twice["used"] == [1]


# --- the executor's account -------------------------------------------------

@pytest.fixture()
def no_persistent_cache():
    """jax's cache key ignores op metadata: an executable cached by a tree
    from before the op-instance scope would be served without it (rows
    then group by type, nothing fails). These compiles are the test's
    own."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _tiny(cell, tmp_path, monkeypatch):
    from benchmarks import run
    from paddle_tpu.ops import pallas_conv

    monkeypatch.setattr(pallas_conv, "PALLAS_CONV", False)
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    xplane.forget_accounts()
    run.measure(cell, seed=2 ** 31 + 35, seconds=0.3, trace=True,
                data_dir=DATA)
    return os.path.join(str(tmp_path), cell)


@pytest.mark.parametrize("cell", ["tiny-gpt2.train", "tiny-resnet18.train",
                                  "tiny-ouro.train"])
def test_account_agrees_with_xla_and_rows_sum_to_busy(cell, tmp_path,
                                                      monkeypatch,
                                                      no_persistent_cache):
    trace_dir = _tiny(cell, tmp_path, monkeypatch)
    steps = {info["program"]: (instrs, info)
             for instrs, info in xplane.known_accounts()}
    assert len(steps) >= 2      # the startup program and the step
    for instrs, info in steps.values():
        mine = sum(i.flops or 0.0 for i in instrs if i.entry)
        assert mine == pytest.approx(info["xla_flops"], rel=0.02)
    # the step's instructions carry their op instance
    (instrs,) = [v[0] for v in steps.values()
                 if any(i.role == "backward" for i in v[0])]
    placed = [i for i in instrs if i.op is not None]
    assert placed and all(i.at is not None for i in placed)
    grads = {i.at for i in instrs if i.op and i.op.endswith("_grad")}
    assert len(grads) >= 4      # which layer's gradient op
    account = xplane.step_account(trace_dir)
    assert account["joined"] == pytest.approx(1.0)
    for step in account["steps"]:
        assert step["host"] is True
        assert sum(r["ms"] for r in step["rows"]) == pytest.approx(
            step["busy_ms"])
    assert os.path.exists(os.path.join(trace_dir, xplane.ACCOUNT_FILE))


def test_a_looped_steps_account_tells_passes_replays_and_exits_apart(
        tmp_path, monkeypatch, no_persistent_cache):
    """One layer's weights are read by three passes and their replays
    (tiny-ouro: 2 layers x 3 passes): the account places each product at
    its own op position, marks the replayed ones with their segment, and
    books the exits' and the attention's instructions under their
    scopes, forward and backward."""
    _tiny("tiny-ouro.train", tmp_path, monkeypatch)
    (instrs,) = [instrs for instrs, _ in xplane.known_accounts()
                 if any(i.role == "backward" for i in instrs)]
    products = [i for i in instrs if i.op == "mul" and i.flops]
    forward = {i.at for i in products if i.recompute is None}
    replayed = {i.at for i in products if i.recompute is not None}
    # q, k, v, o, gate, up, down a layer application and a head an exit;
    # five of the six applications and two of the three exits replayed
    # (the compiler drops a replayed product whose result no gradient
    # reads)
    assert len(forward) == 6 * 7 + 3 and 5 * 5 <= len(replayed) <= 5 * 7 + 2
    assert {i.recompute for i in products} == {None, 1, 2, 3, 4, 5}
    for scope in ("loop_exit", "loop_attention"):
        roles = {i.role for i in instrs if i.scope
                 and scope in i.scope.split(".")}
        assert roles == {"forward", "backward"}, (scope, roles)


def _small_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        hidden = fluid.layers.fc(input=x, size=32, act="relu")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(input=hidden, size=1), y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    feed = {"x": np.ones((8, 16), np.float32),
            "y": np.ones((8, 1), np.float32)}
    return main, startup, loss, feed


def test_account_is_there_after_the_first_run_with_no_second_lowering():
    main, startup, loss, feed = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        assert exe.step_account(main) is None       # before its first run
        exe.run(main, feed=feed, fetch_list=[loss])
        with telemetry.watch_build() as events:
            account = exe.step_account(main)
            exe.run(main, feed=feed, fetch_list=[loss])
            again = exe.step_account(main)
    assert events == []         # nothing traced, lowered or compiled
    assert account is again and account
    ops = {i.op for i in account}
    assert {"mul", "mul_grad", "sgd"} <= ops or "fused_sgd" in ops
    assert all(i.operands == () for i in account)
    assert any(info["program"] == telemetry.program_label(main)
               for _, info in xplane.known_accounts("jit_fn(1)"))


def test_account_is_built_lazily_where_the_memory_analysis_is_off():
    main, startup, loss, feed = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    flags.set("memory_analysis", False)
    try:
        with executor_mod.scope_guard(executor_mod.Scope()):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            (block,) = [c for c in exe._cache.values() if c.program is main]
            assert block.account is None and block.avals is not None
            account = exe.step_account(main)
            assert account and block.account[1] is account
            with telemetry.watch_build() as events:
                assert exe.step_account(main) is account    # once
            assert events == []
    finally:
        flags.set("memory_analysis", True)


def test_planned_step_gives_every_collective_an_axis():
    import jax
    from paddle_tpu.parallel import planner
    from paddle_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    main, startup, loss, feed = _small_program()
    mesh = make_mesh((2, 2), ("fsdp", "tp"), devices=jax.devices()[:4])
    planner.plan(main, mesh, startup=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    with executor_mod.scope_guard(executor_mod.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        account = exe.step_account(main)
    colls = [i for i in account if i.kind]
    assert colls and all(i.axis in ("fsdp", "tp", "fsdp+tp") for i in colls)
    assert all(i.group_size in (2, 4) for i in colls)
