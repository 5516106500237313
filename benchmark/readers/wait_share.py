"""Share of the train loop's time spent in `next(batch)`, in percent
(the arithmetic of `benchmarks/ingest_train_bench.py`)."""


def read(ctx):
    t0, t1 = ctx["run"]["window"]
    return 100.0 * ctx["run"]["wait_s"] / (t1 - t0)
