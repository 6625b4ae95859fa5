"""Shared model-building glue for the zoo."""

from __future__ import annotations

from .. import layers


def build_image_classifier(model_fn, images, label, class_dim=1000, **kwargs):
    """Attach softmax-cross-entropy classification head + accuracy to a
    backbone (the pattern every reference benchmark script repeats,
    e.g. benchmark/paddle/image/resnet.py)."""
    logits = model_fn(images, class_dim=class_dim, **kwargs)
    cost = layers.softmax_with_cross_entropy(logits=logits, label=label)
    avg_cost = layers.mean(cost)
    predict = layers.softmax(logits)
    acc = layers.accuracy(input=predict, label=label)
    return avg_cost, predict, acc


# telemetry side-fetches of a model with expert layers: one series per
# expert layer (label `layer` = its index among them), a sample a step
SIDE_METRICS = ("moe_rows_routed", "moe_rows_combined",
                "moe_load_max_over_mean", "moe_rows_handled")


def side_fetch_marks(program):
    """{gauge or histogram: variable name} the executor fetches beside
    the user's list and publishes off the timed path
    (program._telemetry_fetch_extra), made on first use."""
    marks = getattr(program, "_telemetry_fetch_extra", None)
    if marks is None:
        marks = program._telemetry_fetch_extra = {}
    return marks


def mark_routing_stats(program, stats):
    """Side-fetch what layers.moe_block left in `stats`, one (rows
    routed, rows combined, load, rows handled) tuple an expert layer, as
    SIDE_METRICS: each a vector over the expert layers."""
    for metric, by_layer in zip(SIDE_METRICS, zip(*stats)):
        side_fetch_marks(program)[metric] = layers.concat(
            list(by_layer), axis=0).name


def balance_routers(program, rate):
    """After minimize(): for every moe_router of `program` append the op
    that moves its selection bias against the load the step's choices
    made over all its experts (moe_balance_bias: at most `rate` a step;
    the balancing rule that needs no loss, arXiv:2408.15664), behind the
    backward's and the optimizer's ops and in their role. Behind the
    backward because a gradient op traces its router again and has to
    read the bias the forward read (placed after the router, the rule
    made the cell's first gradient depend on `rate`: PR 42, chip call E).
    A router that the backward replays (`minimize(checkpoints=)`) is the
    same router a second time and gets no second rule. Returns the
    appended ops."""
    from ..backward import RECOMPUTE_ATTR
    block = program.global_block()
    return [block.append_op(
        type="moe_balance_bias",
        inputs={"TopkIdx": op.output("TopkIdx"), "Bias": op.input("Bias")},
        outputs={"BiasOut": op.input("Bias")},
        attrs={"rate": rate, "op_role": "optimize"})
        for op in list(block.ops) if op.type == "moe_router"
        and RECOMPUTE_ATTR not in op.desc.attrs]
