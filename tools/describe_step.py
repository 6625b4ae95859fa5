"""A benchmark cell's train step, compiled for a described v5e (one chip,
or the cell's planner mesh over a described 2 x 2): what the chip's
compiler makes of it, without the chip.

    JAX_PLATFORMS=cpu python3 tools/describe_step.py <cell> [--wider N]
        [--set key=value ...] [--hlo FILE] [--account [TOP]]

Builds the cell's program from its benchmark files (`benchmarks/` is read,
never written), traces the step as Executor.run would (state donated, the
kernels lowered for Mosaic) on avals alone (no startup run, no state on
the host) and prints the compiler's memory analysis, the Mosaic call
count, for a step with checkpoints the segments the executor keeps and
replays under the described chip's memory limit (recompute.py) and every instruction of the entry computation that writes more
than N elements (default 2**28), widest first, with its `op_name`: the
buffers that a memory-bound op's traffic is made of. `--set n_layer=1`
overrides a key of the configuration (a depth, to read one layer fast);
`--hlo FILE` keeps the optimized HLO, to read who consumes a buffer.
`--account` prints instead the step's account by instruction
(`paddle_tpu.xplane.hlo_instructions`): FLOPs, bytes and the least a v5e
could take for each, and their sum, a chipless lower bound of the step
to set against the ledger's busy time, and under it the Mosaic kernels
by name with what each call declares of itself (FLOPs as implemented,
bytes, the floor they make) and the calls that declared nothing. A cell with a `mesh` (`gpt2-large.
train-fsdp2-tp2`; 36 layers take five minutes here, `--set n_layer=2`
half a minute) is planned over described chips as its traffic kind
plans it, its numbers are one chip's, and its collectives are printed by
mesh axis and kind, whole activations apart from the rest (weights,
optimizer state) and by dtype, with instructions, collectives (distinct
`channel_id`s) and MB: the chipless half of
`benchmarks/step_account.py`'s table, which a change to what GSPMD
reduces or gathers is read from before a four-chip call. Nothing runs, so no time
comes from here (PERF.md section 3).
"""

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np



def _described_devices():
    """The chips of a described v5e:2x2, and the kernels steered out of
    the interpreter: the process sees the CPU and would take it."""
    from jax.experimental import topologies
    from paddle_tpu.ops import pallas_attention, pallas_conv

    pallas_attention._interpret = pallas_conv._interpret = lambda: False
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


def described_chip():
    """Chip 0 of a described v5e:2x2 as a sharding."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(_described_devices()[0])


def described_mesh(axes):
    """The planner mesh of a cell (`{"fsdp": 2, "tp": 2}`, in mesh order)
    over the chips of a described v5e:2x2, kernels steered as for
    `described_chip`."""
    from jax.sharding import Mesh

    sizes = tuple(axes.values())
    return Mesh(np.array(_described_devices()[:int(np.prod(sizes))]).reshape(
        sizes), tuple(axes))


def build_step(cell, config, where):
    """(main, startup, loss, feed) of `cell` under `config`; a cell with
    a `mesh` is planned over the described mesh `where` first, as
    `benchmarks/traffic/train_steps_planned.py` plans it."""
    from benchmarks import run

    family = run.load_module("families", config["family"])
    main, startup, loss = family.build(config)
    feed = family.make_batch(config, cell["batch"], np.random.default_rng(0))
    if cell.get("mesh"):
        from paddle_tpu.parallel import planner
        planner.plan(main, where, startup=startup)
    return main, startup, loss, feed


def compile_step(cell, config, where):
    """The compiled train step of `cell` under `config`, for `where`: the
    sharding of one described chip, or for a cell with a `mesh` the
    described mesh."""
    return compile_program(*build_step(cell, config, where), where)


def replay_plan(cell, config, where):
    """What the executor decides of the cell's recomputation segments for
    the described chip `where` (recompute.Plan; None for a step without
    checkpoints): the step traced on avals, nothing compiled."""
    main, startup, loss, feed = build_step(cell, config, where)
    exe, step, avals, _ = _step_on_avals(main, startup, loss, feed, where)
    jax.eval_shape(step, *avals)
    return exe.recompute_plan(main)


def _step_on_avals(main, startup, loss, feed, where):
    """(executor, step function, avals, jit options) of the train step of
    `main` as Executor.run would trace it, the state's avals from
    `startup`, the feed's from `feed`: on the device of the sharding
    `where`, or, where `main` carries a mesh, with the shardings and
    compiler options the executor gives a planned step."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import overlap

    exe = fluid.Executor(fluid.CPUPlace())
    if getattr(main, "_mesh", None) is None:
        # the executor decides what a checkpointed step replays from its
        # device's limit: here the described chip's
        exe.device, = where.device_set

    def step_fn(program, fetch):
        return exe._make_step_fn(program, fetch,
                                 exe._persistable_outputs(program), {})

    rng = np.uint32(0)
    state = jax.eval_shape(step_fn(startup, []), {}, {}, rng)[2]
    options = {}
    if getattr(main, "_mesh", None) is None:
        feed_at, state_at, rng_at = ({n: where for n in feed},
                                     {n: where for n in state}, where)
    else:
        feed_at, state_at, rng_at = exe._shardings(main, list(state),
                                                   list(feed))
        options = {"compiler_options": dict(overlap.TPU_OVERLAP_OPTIONS)}

    def avals(tree, at):
        return {n: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=at[n])
                for n, v in tree.items()}

    return exe, step_fn(main, [loss.name]), (
        avals(feed, feed_at), avals(state, state_at),
        jax.ShapeDtypeStruct((), np.uint32, sharding=rng_at)), options


def compile_program(main, startup, loss, feed, where):
    """The train step of `main` as Executor.run would trace it (state
    donated), compiled from avals alone (`_step_on_avals`)."""
    _, step, avals, options = _step_on_avals(main, startup, loss, feed,
                                             where)
    return jax.jit(step, donate_argnums=(1,), **options).lower(
        *avals).compile()


def tokens_a_chip(compiled):
    """Rows of a whole activation on one chip: the elements one chip
    holds of the step's first feed (the token ids, [batch, T] split over
    the plan's batch axes)."""
    feeds, shardings = compiled.args_info[0][0], compiled.input_shardings[0][0]
    name = min(feeds)
    return int(np.prod(shardings[name].shard_shape(feeds[name].shape)))


def collective_rows(text, axes, rows=None):
    """{(mesh axis, kind, what, dtype): [instructions, collectives, payload
    bytes]} of a planned step's compiled text, a chip. An asynchronous
    collective is one instruction in each computation that holds a piece
    of it, so the collectives are counted by `channel_id`. `what` is
    "activation" where the collective writes a whole activation (an array
    of every one of the chip's `rows` tokens: `[rows, n]` or `[batch, T,
    n]`), "rest" for everything else (weights, optimizer state, heads'
    pieces); `dtype` is the first array's."""
    from paddle_tpu import xplane

    found = {}
    for instr in xplane.hlo_instructions(text, mesh=dict(axes)):
        if not instr.kind:
            continue
        dtype, dims = re.search(r"(\w+)\[([\d,]*)\]", instr.moved).groups()
        lead = [int(d) for d in dims.split(",") if d][:-1]
        what = "activation" if rows and lead and np.prod(lead) == rows \
            else "rest"
        row = found.setdefault((instr.axis, instr.kind, what, dtype),
                               [0, set(), 0])
        row[0] += 1
        row[1].add(instr.channel if instr.channel is not None
                   else instr.name)
        row[2] += instr.payload
    return {key: [n, len(channels), payload]
            for key, (n, channels, payload) in found.items()}


def wide_instructions(text, wider):
    """(elements, dtype[shape]{layout}, instruction name, op_name) of the
    entry computation's instructions that write more than `wider`
    elements: a read of the one parse (`xplane.hlo_instructions`)."""
    from paddle_tpu import xplane

    rows = []
    for instr in xplane.hlo_instructions(text):
        if not instr.entry or instr.opcode == "parameter":
            continue
        elements, shape = xplane.first_array(instr.shape)
        if elements > wider:
            rows.append((elements, shape, "%" + instr.name, instr.op_name))
    return sorted(rows, reverse=True)


def account_rows(text):
    """[(floor ms, instruction)] of the compiled step for a v5e with no
    chip: every instruction of the entry computation that takes time,
    with the least it could take, max(flops / peak, bytes / hbm) from
    chip.py's table; an async half has none, and a Mosaic call's is in
    `kernel_rows`, from what the call declares of itself."""
    from paddle_tpu import chip, xplane

    row = chip.PEAKS["TPU v5 lite"]
    peak, hbm = row.bf16_tflops * 1e12, row.hbm_gbps * 1e9
    rows = []
    for instr in xplane.compact(xplane.hlo_instructions(text)):
        if not instr.entry:
            continue
        floor = xplane.floor_seconds(instr, peak, hbm)
        rows.append((1e3 * floor[0] if floor else 0.0, instr))
    return rows


def kernel_rows(text):
    """{kernel name: [calls, declared FLOPs, declared bytes, floor ms,
    declared_by]} of the compiled step's Mosaic calls for a v5e, from
    what each call declares of itself (`Instr.declared_*`), and the calls
    that declared nothing."""
    from paddle_tpu import chip, xplane

    row = chip.PEAKS["TPU v5 lite"]
    peak, hbm = row.bf16_tflops * 1e12, row.hbm_gbps * 1e9
    kernels, silent = {}, []
    for instr in xplane.hlo_instructions(text):
        if instr.opcode != "custom-call" or instr.flops is not None:
            continue
        floor = xplane.kernel_floor_seconds(instr, peak, hbm)
        if floor is None:
            silent.append(instr.heavy)
            continue
        acc = kernels.setdefault(instr.heavy,
                                 [0, 0.0, 0, 0.0, instr.declared_by])
        acc[0] += 1
        acc[1] += instr.declared_flops
        acc[2] += instr.declared_bytes
        acc[3] += 1e3 * floor[0]
    return kernels, silent


def print_kernels(text):
    """The kernels table of `--account`: an instruction inside a loop or
    a switch's branch counts once, as the account's own rows do."""
    kernels, silent = kernel_rows(text)
    print("\n%-22s %6s %12s %12s %10s  %s" % (
        "Mosaic kernel", "calls", "GFLOP", "MB", "floor ms", "declared by"))
    for name, (n, flops, nbytes, floor, by) in sorted(
            kernels.items(), key=lambda kv: -kv[1][3]):
        print("%-22s %6d %12.2f %12.1f %10.3f  %s" % (
            name, n, flops / 1e9, nbytes / 1e6, floor, by))
    print("%-22s %6d %12.2f %12.1f %10.3f  (calls that declared nothing: "
          "%d%s)" % (
              "sum", sum(k[0] for k in kernels.values()),
              sum(k[1] for k in kernels.values()) / 1e9,
              sum(k[2] for k in kernels.values()) / 1e6,
              sum(k[3] for k in kernels.values()), len(silent),
              " " + ",".join(sorted(set(silent))) if silent else ""))


def print_account(text, top):
    from paddle_tpu import xplane

    rows = account_rows(text)
    by_heavy = {}
    for floor, i in rows:
        acc = by_heavy.setdefault(i.heavy, [0, 0.0, 0, 0.0])
        acc[0] += 1
        acc[1] += i.flops or 0.0
        acc[2] += i.bytes
        acc[3] += floor
    print("%-22s %6s %12s %12s %10s" % ("heavy", "count", "GFLOP", "MB",
                                        "floor ms"))
    for heavy, (n, flops, nbytes, floor) in sorted(
            by_heavy.items(), key=lambda kv: -kv[1][3]):
        print("%-22s %6d %12.2f %12.1f %10.3f" % (heavy, n, flops / 1e9,
                                                  nbytes / 1e6, floor))
    print("%-22s %6d %12.2f %12.1f %10.3f" % (
        "sum", len(rows), sum(i.flops or 0.0 for _, i in rows) / 1e9,
        sum(i.bytes for _, i in rows) / 1e6, sum(f for f, _ in rows)))
    print_kernels(text)
    print("\n%9s %10s %10s  %-12s %-28s %-6s %s" % (
        "floor ms", "GFLOP", "MB", "heavy", "instruction", "at", "op"))
    for floor, i in sorted(rows, key=lambda r: -r[0])[:top]:
        print("%9.4f %10.3f %10.2f  %-12s %-28s %-6s %s/%s %s" % (
            floor, (i.flops or 0.0) / 1e9, i.bytes / 1e6, i.heavy[:12],
            i.name[:28], "-" if i.at is None else i.at, i.role, i.op,
            i.detail or xplane._plain(i.shape)))


def main(argv=None):
    from benchmarks import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--wider", type=int, default=2 ** 28)
    ap.add_argument("--set", action="append", default=[], metavar="key=value")
    ap.add_argument("--hlo", metavar="FILE")
    ap.add_argument("--account", nargs="?", type=int, const=40, default=None,
                    metavar="TOP", help="the step's account by instruction "
                    "for a v5e: FLOPs, bytes and floor ms by what does the "
                    "work, their sum (a chipless lower bound of the step) "
                    "and the TOP instructions by floor")
    args = ap.parse_args(argv)
    cell = run.load_json("workloads", args.cell)
    config = run.load_json("configs", cell["config"])
    config.update((k, json.loads(v)) for k, v in
                  (item.split("=", 1) for item in args.set))
    where = described_mesh(cell["mesh"]) if cell.get("mesh") \
        else described_chip()
    built = build_step(cell, config, where)
    exe, step, avals, options = _step_on_avals(*built, where)
    compiled = jax.jit(step, donate_argnums=(1,), **options).lower(
        *avals).compile()
    decided = exe.recompute_plan(built[0])
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    mem = compiled.memory_analysis()
    print(json.dumps({
        "cell": args.cell, "set": args.set,
        "temp_bytes": mem.temp_size_in_bytes,
        "argument_bytes": mem.argument_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        # what the executor decided of the checkpointed segments for the
        # described chip's limit (recompute.py): the step the chip runs
        **({} if decided is None else {
            "segments_kept": sorted(
                i for i, d in decided.decisions.items() if d.kept),
            "segments_replayed": sorted(
                i for i, d in decided.decisions.items() if not d.kept),
            "kept_bytes": decided.kept_bytes,
            "estimated_bytes": decided.estimate,
            "limit_bytes": decided.limit})}))
    if cell.get("mesh"):
        print("%-10s %-20s %-10s %-6s %6s %6s %10s" % (
            "axis", "kind", "what", "dtype", "instrs", "colls", "MB"))
        for (axis, kind, what, dtype), (runs, colls, payload) in sorted(
                collective_rows(text, cell["mesh"],
                                tokens_a_chip(compiled)).items(),
                key=lambda kv: -kv[1][2]):
            print("%-10s %-20s %-10s %-6s %6d %6d %10.2f" % (
                axis or "-", kind, what, dtype, runs, colls, payload / 1e6))
    if args.account is not None:
        print_account(text, args.account)
        return
    for elements, shape, name, op_name in wide_instructions(text, args.wider):
        print("%14d  %-34s %-40s %s" % (elements, shape, name, op_name))


if __name__ == "__main__":
    main()
