"""Host time of one ``Executor.run`` as the program itself takes it: the
median of the ``executor.run_seconds`` histogram, which the
``executor.run`` span observes from inside the call (prepare, dispatch,
finish; the step is dispatched, not awaited).  Some 80 calls in a run:
the start-up and warm-up calls do not move a median.  The training
runner hands over no registry snapshot, so this reads the process's
global registry; ``executor.dispatch_ms`` beside it is the same interval
timed by the runner from outside."""

NAME = "executor.run_host_ms"
LAYER = "Program lowering"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"
RUNNERS = ("train",)


def read(facts):
    from paddle_tpu.observability import get_registry

    hist = get_registry().get("executor.run_seconds", kind="histogram")
    if hist is None or not hist.count:
        return None
    return hist.percentile(50) * 1e3
