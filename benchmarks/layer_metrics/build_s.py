"""Seconds of set-up inside a ``fedml.build`` span (a simulator's
constructor: model creation, banks, placing the population and the test
set; ``ShardedFedAvg``'s holds ``FedAvgSim``'s, counted once): the
inside twin of ``data_build_s``, which also times the benchmark's own
population generator. Host ring."""

from lib import host_ring


def read(ctx):
    return host_ring.metric(ctx, "build_s")
