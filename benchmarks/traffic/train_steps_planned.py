"""Traffic kind `train_steps_planned`: `train_steps` on a program the
sharding planner has laid out over the cell's chips. The cell's `mesh`
names the planner's axes and their sizes in mesh order, `{"fsdp": 2,
"tp": 2}`: the first `chips` devices of the host, reshaped to those
sizes (`parallel.mesh.make_mesh`), with the global batch split over
`fsdp` (data parallelism with ZeRO-sharded state) and the matrices over
`fsdp` x `tp` by role (`parallel/planner.py`). The product of the sizes
is the cell's `chips`.

The family's `build` is followed by `planner.plan(main, mesh,
startup=startup)`, as `chip_smoke.multichip_phase` plans, so the
start-up program creates the state sharded; everything else (the feeder,
the steps in flight, the float32 reference on the first step, the window,
the traced steps) is `train_steps.run`, which gathers a sharded
parameter to the host like any other array. The evidence gains `plan`:
what the planner decided, by role and shard factor, the tensors whose
layout it degraded by name, and the fallback counters of the planner
and of the fused optimizer apply.
"""

from benchmarks.traffic import train_steps


class Planned:
    """`family` with a `build` that plans the program it built over the
    cell's mesh and keeps the Plan in `self.plan`; everything else is the
    family's own."""

    def __init__(self, family, cell):
        self.family, self.cell, self.plan = family, cell, None

    def __getattr__(self, name):
        return getattr(self.family, name)

    def build(self, config):
        import jax
        from paddle_tpu.parallel import planner
        from paddle_tpu.parallel.mesh import make_mesh

        main, startup, loss = self.family.build(config)
        axes = self.cell["mesh"]
        mesh = make_mesh(tuple(axes.values()), tuple(axes),
                         devices=jax.devices()[:self.cell["chips"]])
        self.plan = planner.plan(main, mesh, startup=startup)
        return main, startup, loss


def plan_record(plan):
    """What the plan decided, for the run's record: the planner's gauges
    (parameters, and bytes a chip holds of them, by role and shard
    factor; a program without them leaves the rows empty), each degraded
    tensor by name, and the fallback counters."""
    from paddle_tpu import telemetry

    return {
        "mesh_axes": list(plan.mesh_axes),
        "params": telemetry.read_series("planner_params"),
        "bytes_per_chip": telemetry.read_series("planner_shard_bytes"),
        "bytes_per_chip_total": plan.per_shard_bytes,
        "bytes_total": plan.total_bytes,
        "degraded": {p.name: list(p.notes) for p in plan.params.values()
                     if p.notes},
        "planner_fallback_total":
            telemetry.read_series("planner_fallback_total"),
        "fusion_fallback_total": {
            k: v for k, v in
            telemetry.read_series("fusion_fallback_total").items()
            if "sharded_param" in k}}


def run(cell, config, family, seconds, seed, trace_dir):
    planned = Planned(family, cell)
    ev = train_steps.run(cell, config, planned, seconds, seed, trace_dir)
    ev["plan"] = plan_record(planned.plan)
    return ev
