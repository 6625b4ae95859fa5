"""Communication/compute overlap (parallel/overlap.py, ISSUE 9): the
bucketed eager gradient sync must be BITWISE invisible to numerics, the
bucket plan deterministic, every skip reason counted, the compile-layer
options gated off non-TPU backends, and the auto steps-per-call bounded
by both the amortization and the memory model.

The per-bucket `pd.coll.dp_grad_bucket<i>` sites are pinned through the
synthetic-xplane path (test_fleet's hand-rolled encoder): real compiled
HLO attributes GSPMD's dp-grad all-reduces to the producer grad ops (the
constraint nodes fuse away — see the module docstring caveat), so the
reporting contract is asserted against traces that carry the sites."""

import types

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as em
from paddle_tpu import fleet, telemetry, xplane
from paddle_tpu.framework import unique_name
from paddle_tpu.parallel import overlap

from test_fleet import (_event, _line, _meta, _plane,  # noqa: F401
                        _write_xspace, pinned_ici)


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    telemetry.reset()
    old = overlap.OVERLAP_OPT
    yield
    overlap.OVERLAP_OPT = old
    overlap._PLANS.clear()
    telemetry.reset()


def _with_overlap(on, fn, *args, **kw):
    """Run fn under OVERLAP_OPT=on. Callers build a FRESH program inside
    fn — the jit and plan caches key on program identity."""
    old = overlap.OVERLAP_OPT
    overlap.OVERLAP_OPT = on
    try:
        return fn(*args, **kw)
    finally:
        overlap.OVERLAP_OPT = old


def _fallbacks(reason=None):
    series = telemetry.read_series("overlap_fallback_total")
    if reason is None:
        return sum(series.values())
    return sum(v for k, v in series.items() if f"reason={reason}" in k)


def _state(scope):
    return {n: np.asarray(scope.find_var(n))
            for n in scope.local_var_names()
            if isinstance(scope.find_var(n), np.ndarray)
            or hasattr(scope.find_var(n), "dtype")}


def _assert_state_equal(a, b):
    assert set(a) == set(b), set(a) ^ set(b)
    for n in sorted(a):
        np.testing.assert_array_equal(np.asarray(a[n]), np.asarray(b[n]),
                                      err_msg=f"state '{n}' diverged")


def _build_fc(main, startup):
    x = fluid.layers.data(name="x", shape=[12], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    logits = fluid.layers.fc(input=h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    feed = lambda rng: {                                    # noqa: E731
        "x": rng.standard_normal((8, 12)).astype(np.float32),
        "label": rng.integers(0, 4, (8, 1)).astype(np.int64)}
    return loss, feed


def _build_conv(main, startup):
    img = fluid.layers.data(name="img", shape=[3, 8, 8], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    c = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                            padding=1)
    p = fluid.layers.pool2d(input=c, global_pooling=True, pool_type="avg")
    logits = fluid.layers.fc(input=p, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    feed = lambda rng: {                                    # noqa: E731
        "img": rng.standard_normal((8, 3, 8, 8)).astype(np.float32),
        "label": rng.integers(0, 4, (8, 1)).astype(np.int64)}
    return loss, feed


def _train(build, ndev, steps=3):
    """Fresh program each call; dp mesh over the first ndev devices."""
    import jax
    from jax.sharding import Mesh

    unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        loss, make_feed = build(main, startup)
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(
            loss, startup_program=startup)
    main._mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(3)
    scope = em.Scope()
    losses = []
    with em.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            out, = exe.run(main, feed=make_feed(rng), fetch_list=[loss])
            losses.append(float(np.ravel(out)[0]))
        state = _state(scope)
    return losses, state


@pytest.mark.parametrize("build", [_build_fc, _build_conv],
                         ids=["fc", "conv"])
@pytest.mark.parametrize("ndev", [1, 8])
def test_training_parity_bitwise(build, ndev, monkeypatch):
    """The eager bucket flush is a pure sharding annotation: losses AND
    full optimizer state bitwise equal with the pass on vs off, single
    device and across the dp mesh — with the cap shrunk so even these
    KB-sized models split into several buckets."""
    monkeypatch.setenv("PADDLE_TPU_OVERLAP_BUCKET_MB", "0.0001")
    l1, s1 = _with_overlap(True, _train, build, ndev)
    l0, s0 = _with_overlap(False, _train, build, ndev)
    assert l1 == l0
    _assert_state_equal(s1, s0)
    # the overlapped run actually flushed buckets (not a vacuous pass)
    assert sum(telemetry.read_series("overlap_buckets_total").values()) > 0


class TestPlan:
    def _program(self):
        import jax
        from jax.sharding import Mesh

        unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss, _ = _build_fc(main, startup)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(
                loss, startup_program=startup)
        main._mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        return main

    def test_deterministic_and_readiness_ordered(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_OVERLAP_BUCKET_MB", "0.0001")
        prog = self._program()
        a, b = overlap._build(prog), overlap._build(prog)
        assert [x.grads for x in a.buckets] == [x.grads for x in b.buckets]
        assert [x.site for x in a.buckets] == [x.site for x in b.buckets]
        # sites numbered in flush (anchor) order
        assert a.sites == [f"dp_grad_bucket{i}"
                           for i in range(len(a.buckets))]
        anchors = [x.anchor for x in a.buckets]
        assert anchors == sorted(anchors)
        # tiny cap: the 4 param grads (2 fc layers) split across buckets
        assert len(a.buckets) >= 2

    def test_plan_cached_per_program(self):
        prog = self._program()
        assert overlap.plan(prog) is overlap.plan(prog)

    def test_no_plan_without_dp_mesh(self):
        unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss, _ = _build_fc(main, startup)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(
                loss, startup_program=startup)
        assert overlap.plan(main) is None          # no mesh at all

    def test_gate_off_no_plan(self):
        prog = self._program()
        assert _with_overlap(False, overlap.plan, prog) is None

    def test_tp_sharded_param_falls_back(self):
        """Model-parallel (tensor-sharded) grads hold different values
        per shard — no cross-dp sum to schedule, counted tp_sharded."""
        prog = self._program()
        some_param = prog.global_block().all_parameters()[0].name
        prog._param_shardings = {some_param: (None, "mp")}
        p = overlap._build(prog)
        assert _fallbacks("tp_sharded") == 1
        assert all(some_param not in b.params for b in p.buckets)

    def test_unknown_axis_param_keeps_sharded_param_reason(self):
        """A spec naming an axis this mesh lacks can't be pinned — the
        historical sharded_param reason stays for dashboards."""
        prog = self._program()
        some_param = prog.global_block().all_parameters()[0].name
        prog._param_shardings = {some_param: ("fsdp", None)}  # dp-only mesh
        p = overlap._build(prog)
        assert _fallbacks("sharded_param") == 1
        assert all(some_param not in b.params for b in p.buckets)

    def test_dp_sharded_param_buckets_per_spec_group(self):
        """ISSUE 15: a ZeRO/dp-sharded param no longer skips — its grad
        buckets in its OWN (dtype, spec) group, never mixed with
        replicated grads, and the bucket records the spec to pin to."""
        prog = self._program()
        some_param = prog.global_block().all_parameters()[0].name
        prog._param_shardings = {some_param: ("dp", None)}
        p = overlap._build(prog)
        assert _fallbacks("sharded_param") == 0
        assert _fallbacks("tp_sharded") == 0
        with_param = [b for b in p.buckets if some_param in b.params]
        assert len(with_param) == 1
        assert with_param[0].spec == ("dp",)
        # replicated grads keep the empty spec and never share a bucket
        for b in p.buckets:
            if some_param not in b.params:
                assert b.spec == ()


class TestFlushFallbacks:
    def _ctx(self):
        import jax
        from jax.sharding import Mesh

        prog = types.SimpleNamespace(
            _mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)))
        return types.SimpleNamespace(program=prog)

    def test_sparse_grad_keeps_selected_rows(self):
        from paddle_tpu.ops.common import SelectedRowsVal
        import jax.numpy as jnp

        sr = SelectedRowsVal(rows=jnp.array([0, 1], jnp.int32),
                             values=jnp.ones((2, 3), jnp.float32),
                             height=5)
        env = {"emb@GRAD": sr}
        b = overlap.Bucket(index=0, params=("emb",), grads=("emb@GRAD",),
                           dtype="float32", bytes=24, anchor=0)
        overlap._flush(self._ctx(), b, env)
        assert env["emb@GRAD"] is sr               # untouched
        # no optimizer consumer is known for this synthetic program, so
        # the refined reason is "unsupported" (a real sgd/momentum/adam
        # consumer would count sparse_grad_handled instead)
        assert _fallbacks("sparse_grad_unsupported") == 1

    def test_missing_grad_counted(self):
        b = overlap.Bucket(index=0, params=("w",), grads=("w@GRAD",),
                           dtype="float32", bytes=4, anchor=0)
        overlap._flush(self._ctx(), b, {})
        assert _fallbacks("missing_grad") == 1


class TestCompilerOptions:
    def test_cpu_backend_counts_platform(self):
        import jax
        assert jax.default_backend() != "tpu"      # test-suite invariant
        assert overlap.compiler_options(
            types.SimpleNamespace(_mesh=object())) is None
        assert _fallbacks("platform") == 1

    def test_no_mesh_no_options(self):
        assert overlap.compiler_options(
            types.SimpleNamespace(_mesh=None)) is None
        assert _fallbacks() == 0                    # silent: nothing to do

    def test_gate_off_no_options(self):
        assert _with_overlap(
            False, overlap.compiler_options,
            types.SimpleNamespace(_mesh=object())) is None
        assert _fallbacks() == 0

    def test_env_override_rejected_by_probe(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_OVERLAP_XLA_FLAGS",
                           "xla_definitely_not_an_option_zzz=true")
        overlap._VALIDATED.clear()
        try:
            assert overlap.compiler_options(
                types.SimpleNamespace(_mesh=object())) is None
            assert _fallbacks("rejected_options") == 1
        finally:
            overlap._VALIDATED.clear()
        # the verdict is cached: a second ask does not re-probe but still
        # counts the fallback
        overlap._VALIDATED[(
            ("xla_definitely_not_an_option_zzz", "true"),)] = False
        assert overlap.compiler_options(
            types.SimpleNamespace(_mesh=object())) is None
        assert _fallbacks("rejected_options") == 2
        overlap._VALIDATED.clear()

    def test_env_override_bypasses_platform_gate(self, monkeypatch):
        """A validated env-provided set is returned even off-TPU (the
        escape hatch for flag experiments on any backend)."""
        monkeypatch.setenv("PADDLE_TPU_OVERLAP_XLA_FLAGS",
                           "xla_k=v, xla_k2")
        monkeypatch.setattr(overlap, "_validate", lambda opts: True)
        assert overlap.compiler_options(
            types.SimpleNamespace(_mesh=object())) == {
            "xla_k": "v", "xla_k2": "true"}

    def test_empty_env_override_disables(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_OVERLAP_XLA_FLAGS", "")
        assert overlap.compiler_options(
            types.SimpleNamespace(_mesh=object())) is None
        assert _fallbacks() == 0

    def test_probe_accepts_empty_options(self):
        assert overlap._validate({}) is True


class TestChooseStepsPerCall:
    def test_no_signals_means_hi(self):
        assert overlap.choose_steps_per_call() == 64
        assert overlap.choose_steps_per_call(hi=16) == 16

    def test_amortization_ceiling(self):
        # 1ms dispatch over 10ms steps at 2% target -> ceil(1/0.2) = 5
        assert overlap.choose_steps_per_call(
            python_overhead_ms=1.0, step_time_ms=10.0) == 5

    def test_memory_cap_shrinks(self):
        # headroom (3MB budget - 1MB fixed) / 1MB per window = 2 < the
        # amortization ask of 5
        mb = 1 << 20
        assert overlap.choose_steps_per_call(
            python_overhead_ms=1.0, step_time_ms=10.0,
            feed_bytes_per_step=mb, peak_bytes=2 * mb,
            budget_bytes=3 * mb) == 2

    def test_clamped_to_bounds(self):
        assert overlap.choose_steps_per_call(
            python_overhead_ms=0.001, step_time_ms=100.0, lo=4) == 4
        assert overlap.choose_steps_per_call(
            python_overhead_ms=100.0, step_time_ms=1.0, hi=8) == 8

    def test_memory_only_bounds_from_hi(self):
        mb = 1 << 20
        assert overlap.choose_steps_per_call(
            feed_bytes_per_step=mb, peak_bytes=2 * mb,
            budget_bytes=12 * mb) == 11


# --- per-bucket sites + exposure through the reporting path -----------------

_HLO_MONO = """\
HloModule jit_step

ENTRY main {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %all-reduce.1 = f32[2048,1024]{1,0} all-reduce(%p0), channel_id=1, \
replica_groups=[1,4]<=[4], to_apply=%add, \
metadata={op_name="jit(step)/pd.mul_grad/pd.coll.dp_grad/add"}
}
"""

_HLO_BUCKETED = """\
HloModule jit_step

ENTRY main {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %all-reduce.1 = f32[1024,1024]{1,0} all-reduce(%p0), channel_id=1, \
replica_groups=[1,4]<=[4], to_apply=%add, \
metadata={op_name="jit(step)/pd.fc_grad/pd.coll.dp_grad_bucket0/add"}
  %all-reduce.2 = f32[1024,1024]{1,0} all-reduce(%p0), channel_id=2, \
replica_groups=[1,4]<=[4], to_apply=%add, \
metadata={op_name="jit(step)/pd.conv2d_grad/pd.coll.dp_grad_bucket1/add"}
}
"""


def _write_mono(tmp_path):
    # one monolithic post-backward all-reduce, nothing left to overlap
    # with: 8us, fully exposed
    metas = [_meta(1, "fusion.1"), _meta(2, "all-reduce.1")]
    raw = _line("XLA Ops", 0, [
        _event(1, 0, 2_000_000),               # backward: 0..2us
        _event(2, 2_000_000, 8_000_000),       # all-reduce.1: 2..10us
    ])
    d = tmp_path / "mono"
    d.mkdir()
    _write_xspace(d / "t.xplane.pb", [_plane("/device:TPU:0", [raw], metas)])
    return str(d)


def _write_bucketed(tmp_path):
    # same 8us of all-reduce split across two eager buckets: bucket0
    # launches while backward still computes (fully hidden), bucket1
    # trails the last grad op with only 2us exposed
    metas = [_meta(1, "fusion.1"), _meta(2, "all-reduce.1"),
             _meta(3, "all-reduce.2")]
    raw = _line("XLA Ops", 0, [
        _event(1, 0, 6_000_000),               # backward: 0..6us
        _event(2, 1_000_000, 4_000_000),       # bucket0: 1..5us, hidden
        _event(3, 6_000_000, 4_000_000),       # bucket1: 6..10us, exposed
    ])
    d = tmp_path / "bucketed"
    d.mkdir()
    _write_xspace(d / "t.xplane.pb", [_plane("/device:TPU:0", [raw], metas)])
    return str(d)


class TestBucketSitesInFleetReport:
    def test_buckets_split_sites_and_cut_exposure(self, tmp_path,
                                                  pinned_ici):
        """The ISSUE 9 acceptance shape: dp-grad collectives appear under
        >= 2 per-bucket sites, and the bucketed schedule's exposed
        fraction beats the monolithic one at equal payload+time."""
        mono = fleet.collective_table(
            _write_mono(tmp_path), probe=False,
            accounts=[xplane.hlo_instructions(_HLO_MONO)])
        buck = fleet.collective_table(
            _write_bucketed(tmp_path), probe=False,
            accounts=[xplane.hlo_instructions(_HLO_BUCKETED)])
        sites = {r["site"] for r in buck["rows"]}
        assert {"dp_grad_bucket0", "dp_grad_bucket1"} <= sites
        def total_and_exposed(table):
            return (sum(r["time_ms"] for r in table["rows"]),
                    sum(r["exposed_ms"] for r in table["rows"]))

        total_m, exposed_m = total_and_exposed(mono)
        total_b, exposed_b = total_and_exposed(buck)
        # identical 8us of collective time in both scenarios...
        assert total_m == pytest.approx(total_b)
        # ...but the bucketed one hides half of it
        assert exposed_m == pytest.approx(total_m)
        assert exposed_b == pytest.approx(total_b / 2)


class TestExecutorIntegration:
    def test_plan_used_by_trace(self, monkeypatch):
        """End-to-end through Executor.run on the dp mesh: the flush
        counter moves, proving the trace loop consults the plan (not just
        plan() in isolation)."""
        monkeypatch.setenv("PADDLE_TPU_OVERLAP_BUCKET_MB", "0.0001")
        _with_overlap(True, _train, _build_fc, 8, 1)
        series = telemetry.read_series("overlap_buckets_total")
        assert sum(series.values()) >= 2        # >= 2 buckets flushed

    def test_merged_sibling_gradients_leave_the_buckets_where_they_were(
            self, monkeypatch):
        """ISSUE 48: on dp x tp the input gradients of q, k and v are
        traced as one contraction (ops/sibling_products.py). The plan
        reads the ProgramDesc, which the merge does not touch: the same
        buckets at the same anchors with the matcher's table emptied, each
        of the three weights' gradients still written by its own mul_grad
        (and left to GSPMD as `tp_sharded`), and the trace flushes every
        bucket it flushed before."""
        import jax

        import chip_smoke
        from paddle_tpu.ops import sibling_products
        from paddle_tpu.parallel import planner
        from paddle_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 4:
            pytest.skip("needs four (virtual) devices")
        monkeypatch.setenv("PADDLE_TPU_OVERLAP_BUCKET_MB", "0.001")

        def traced():
            telemetry.reset()
            main, startup, loss = chip_smoke._build_lm(
                seqlen=32, d_model=32, n_head=2, n_layer=2, vocab=101,
                use_flash=False)
            planner.plan(main, make_mesh((2, 2), ("dp", "tp"),
                                         devices=jax.devices()[:4]))
            plan = overlap.plan(main)
            exe = fluid.Executor(fluid.CPUPlace())
            with em.scope_guard(em.Scope()):
                exe.run(startup)
                exe.run(main, feed=chip_smoke._lm_feed(4, 32, 101),
                        fetch_list=[loss])
            ops = main.global_block().ops
            writers = {n: op.type for op in ops
                       for n in op.desc.output_arg_names()
                       if n.endswith(".w_0@GRAD")}
            return ([(b.params, b.grads, b.anchor, b.spec)
                     for b in plan.buckets], writers,
                    sum(telemetry.read_series(
                        "overlap_buckets_total").values()),
                    _fallbacks("tp_sharded"),
                    sum(telemetry.read_series(
                        "sibling_products_merged_total").values()))

        with_rule = _with_overlap(True, traced)
        monkeypatch.setattr(sibling_products, "SIBLING_OPS", frozenset())
        without = _with_overlap(True, traced)
        assert with_rule[4] == 2 and without[4] == 0
        assert with_rule[:4] == without[:4]
        buckets, writers, flushed, tp_sharded, _ = with_rule
        assert buckets and flushed >= len(buckets) and tp_sharded >= 6
        assert {writers["fc_%d.w_0@GRAD" % i] for i in range(3)} == {
            "mul_grad"}
