"""Plan executor: median host time of one round's submit lane (assemble +
H2D + dispatch), the program's ``StatsAggregator`` for
``exchange.pipeline.submit`` (its reservoir spans the warm-up job too), ms."""


def read(run):
    p50 = run.stats_after["submit_p50_ns"]
    return None if p50 is None else p50 / 1e6
