"""Host time one ``Executor.run`` takes to return, unblocked: feed
transfer, state gathering and the dispatch of the step's executable."""

import statistics

NAME = "executor.dispatch_ms"
LAYER = "Program lowering"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"
RUNNERS = ("train",)


def read(facts):
    if not facts.get("dispatch_s"):
        return None
    return statistics.median(facts["dispatch_s"]) * 1e3
