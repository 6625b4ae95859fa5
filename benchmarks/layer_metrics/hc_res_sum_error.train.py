"""The largest distance of a hyper-connection's residual map from doubly
stochastic over the window's and the traced steps (family `xing4`): the
program's gauge `hc_res_sum_error`, a float32 scalar a step (the largest
|row sum - 1| or |column sum - 1| of any H_res, over tokens and
sublayers, a telemetry side-fetch published without a host sync), of
which this is the largest sample: each publication is a `side_fetch`
event of the step log, and the last `steps` + `trace_steps` of them are
the window's and the traced steps'. Twenty sweeps leave the rows at 1 to
rounding and the columns as far as they have converged: 1e-6 and less
near the identity the maps start from, more where training has moved a
map's logits apart. A map off the manifold lets the streams' norm grow
or shrink with depth, which is what the constraint is there to stop.
None where the program publishes no such gauge (a parent program)."""

LAYER = "residual path"
UNIT = "abs"
MOVES = "train_items_per_s"
SOURCE = "program_counter"

GAUGE = "hc_res_sum_error"


def compute(ev):
    from paddle_tpu import telemetry

    samples = [e["values"][0]
               for e in telemetry.recent_events(kind="side_fetch")
               if e.get("metric") == GAUGE and e.get("values")]
    tail = samples[-(ev["steps"] + ev["cell"]["trace_steps"]):]
    return max(tail) if tail else None
