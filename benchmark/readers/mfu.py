"""Model FLOP/s utilisation in percent: tokens per second in the
(untraced) window times the FLOPs a token requires, over the chips
times the published peak. Recomputation and unchosen experts do not
count as required."""

from benchmark.harness.manifest import plugin


def read(ctx):
    run, config = ctx["run"], ctx["cell"].config
    t0, t1 = run["window"]
    flops = plugin("flops", config["flops"]).train_flops_per_token(
        config, run["seq"])
    peak = ctx["device"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * run["tokens"] / (t1 - t0) * flops \
        / (ctx["device"]["count"] * peak)
