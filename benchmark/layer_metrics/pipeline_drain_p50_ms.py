"""Plan executor: median host time of one round's drain lane (wait + D2H), the
program's ``StatsAggregator`` for ``exchange.pipeline.drain`` (its reservoir
spans the warm-up job too), ms."""


def read(run):
    p50 = run.stats_after["drain_p50_ns"]
    return None if p50 is None else p50 / 1e6
