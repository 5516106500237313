"""Share of their roofline that a set of kernels reached, in percent:
the least time the chip could take for the calls the trace shows (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
`benchmark/flops/` and `benchmark/peaks.json`) over their device time.
Prints which bound holds for each kernel."""

from benchmark.harness import trace
from benchmark.harness.manifest import plugin


def read(ctx, kernels):
    if ctx["trace"] is None:
        return None
    config, run = ctx["cell"].config, ctx["run"]
    flops = plugin("flops", config["flops"])
    seen = trace.kernel_seconds(ctx["trace"], kernels)
    least = spent = 0.0
    for kernel, (seconds, calls) in seen.items():
        if not calls:
            continue
        ops, nbytes = flops.flash_ops_and_bytes(
            kernel, batch=run["batch"] // ctx["device"]["count"],
            seq=run["seq"], n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["hidden_size"] // config["num_attention_heads"])
        t, bound = flops.least_seconds(ops, nbytes, ctx["device"]["peaks"])
        print(f"  {kernel}: {calls} calls, {1e3 * seconds / calls:.3f} ms a "
              f"call, least {1e3 * t:.3f} ms ({bound}-bound)")
        least += t * calls
        spent += seconds
    return 100.0 * least / spent if spent else None
