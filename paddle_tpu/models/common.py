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
