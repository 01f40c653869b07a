"""The last value of a gauge in the program's own metrics registry
(``chainermn_tpu.observability.metrics.registry()``, the process-wide one a
scheduler publishes into), times ``scale``.  ``args``: ``name`` (the gauge's),
``scale`` (1 by default).  A program without the registry or without that
gauge, a gauge never set, or one that reads 0, reports nothing."""


def reduce(facts, args):
    try:
        from chainermn_tpu.observability import metrics
    except ImportError:
        return None
    peek = getattr(metrics.registry(), "peek", None)
    gauge = peek(args["name"]) if peek is not None else None
    value = gauge.to_dict().get("value") if gauge is not None else None
    return value * args.get("scale", 1.0) if value else None
