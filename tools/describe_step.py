"""A one-chip benchmark cell's train step, compiled for a described v5e:
what the chip's compiler makes of it, without the chip.

    JAX_PLATFORMS=cpu python3 tools/describe_step.py <cell> [--wider N]
        [--set key=value ...] [--hlo FILE]

Builds the cell's program from its benchmark files (`benchmarks/` is read,
never written), traces the step as Executor.run would (state donated, the
kernels lowered for Mosaic) on avals alone (no startup run, no state on
the host) and prints the compiler's memory analysis, the Mosaic call
count and every instruction of the entry computation that writes more
than N elements (default 2**28), widest first, with its `op_name`: the
buffers that a memory-bound op's traffic is made of. `--set n_layer=1`
overrides a key of the configuration (a depth, to read one layer fast);
`--hlo FILE` keeps the optimized HLO, to read who consumes a buffer.
Nothing runs, so no time comes from here (PERF.md section 3).
"""

import argparse
import json
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

SHAPE = re.compile(r"= \(?(\w+)\[([\d,]+)\](?:\{([\d,]+))?")


def described_chip():
    """Chip 0 of a described v5e:2x2, and the kernels steered out of the
    interpreter: the process sees the CPU and would take it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import pallas_attention, pallas_conv

    pallas_attention._interpret = pallas_conv._interpret = lambda: False
    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def compile_step(cell, config, chip):
    """The compiled train step of `cell` under `config`, for the device
    of the sharding `chip`."""
    from benchmarks import run

    family = run.load_module("families", config["family"])
    main, startup, loss = family.build(config)
    feed = family.make_batch(config, cell["batch"], np.random.default_rng(0))
    return compile_program(main, startup, loss, feed, chip)


def compile_program(main, startup, loss, feed, chip):
    """The train step of `main` as Executor.run would trace it (state
    donated), compiled for the device of the sharding `chip` from avals
    alone: the state's from `startup`, the feed's from `feed`."""
    import paddle_tpu as fluid

    exe = fluid.Executor(fluid.CPUPlace())

    def step_fn(program, fetch):
        return exe._make_step_fn(program, fetch,
                                 exe._persistable_outputs(program), {})

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip),
            tree)

    rng = np.uint32(0)
    state = jax.eval_shape(step_fn(startup, []), {}, {}, rng)[2]
    return jax.jit(step_fn(main, [loss.name]), donate_argnums=(1,)).lower(
        on_chip(feed), on_chip(state), on_chip(rng)).compile()


def wide_instructions(text, wider):
    """(elements, dtype[shape], instruction name, op_name) of the entry
    computation's instructions that write more than `wider` elements."""
    entry = text[text.index("\nENTRY "):]
    rows = []
    for line in entry.splitlines()[1:]:
        shape = SHAPE.search(line)
        if shape is None or "parameter(" in line:
            continue
        dtype, dims, layout = shape.groups()
        elements = math.prod(int(n) for n in dims.split(","))
        if elements > wider:
            op_name = re.search(r'op_name="([^"]*)"', line)
            rows.append((elements, "%s[%s]" % (dtype, dims)
                         + ("{%s}" % layout if layout else ""),
                         line.split("=")[0].strip().removeprefix("ROOT "),
                         op_name.group(1) if op_name else ""))
    return sorted(rows, reverse=True)


def main(argv=None):
    from benchmarks import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--wider", type=int, default=2 ** 28)
    ap.add_argument("--set", action="append", default=[], metavar="key=value")
    ap.add_argument("--hlo", metavar="FILE")
    args = ap.parse_args(argv)
    cell = run.load_json("workloads", args.cell)
    if cell["chips"] != 1:
        sys.exit("%s runs on %d chips: a planned step needs its mesh "
                 "(.claude/skills/verify, recipe 4)" % (args.cell, cell["chips"]))
    config = run.load_json("configs", cell["config"])
    config.update((k, json.loads(v)) for k, v in
                  (item.split("=", 1) for item in args.set))
    compiled = compile_step(cell, config, described_chip())
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
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"')}))
    for elements, shape, name, op_name in wide_instructions(text, args.wider):
        print("%14d  %-34s %-40s %s" % (elements, shape, name, op_name))


if __name__ == "__main__":
    main()
