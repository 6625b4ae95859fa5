"""Device idle time a traced step, in ms, that fell while the host was in
the executor's own bookkeeping: the idle gaps of the trace that
`program_trace.label_gaps` puts down to `pd.bookkeep` or to a
`pd.sink.<name>` span inside it (the innermost program span over each
stretch of a gap), summed over the trace and divided by its `pd.step`s.
What the device loses to the program's own watchers and sinks. A gap
under `launch`, `writeback`, no span at all, or `prepare` and its three
sinks (`sink.gather`, `sink.validate`, `sink.signature`: the step's own
arguments, no watcher's work; a parent's trace calls the same stretch
`prepare`) is not theirs. None without a trace or without `pd.step`
spans."""

from benchmarks import program_trace

LAYER = "device"
UNIT = "ms"
MOVES = "train_items_per_s"
SOURCE = "device_trace"
PREPARE_SINKS = ("sink.gather", "sink.validate", "sink.signature")


def compute(ev):
    reduced = program_trace.of_evidence(ev)
    if reduced is None or not reduced["host_steps"]:
        return None
    idle = sum(seconds for label, seconds in reduced["idle_gaps"].items()
               if label == "bookkeep" or (label.startswith("sink.")
                                          and label not in PREPARE_SINKS))
    return 1e3 * idle / len(reduced["host_steps"])
