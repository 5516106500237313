"""Share of their roofline that the trained flash kernels reached, in
percent, where a step's calls are of two kinds: the least time the chip
could take for the calls the trace shows inside the runs of `program`
on the first device (`flash_ops_and_bytes` of
`benchmark/flops/<flops>.py`, which counts the keys a row sees: every
key before it, or `sliding_window_size` of them where the call's scope
path holds `scope`), over their device time. Batch and rows of a call
are read from its result in the trace, the heads and the head size
from the cell's file. Prints the calls by kernel and
kind. A program without the kernels, or a file without the keys (the
parent's), gives None."""

from benchmark.harness import spans as sp
from benchmark.harness.manifest import plugin
from benchmark.readers.flash_prefill_roofline import calls_seen, kernel_calls


def read(ctx, program, kernels, scope, flops):
    if ctx["trace"] is None:
        return None
    config = ctx["cell"].config
    if "sliding_window_size" not in config:
        return None
    counts = plugin("flops", flops)
    path = sp.xplane_path(ctx)
    names = sp.op_names(path)
    least = spent = 0.0
    for kernel in kernels:
        calls = kernel_calls(path, kernel)
        seen = calls_seen(ctx["trace"], names, calls, program, scope)
        for (batch, rows, windowed), (n, seconds) in sorted(seen.items()):
            ops, nbytes = counts.flash_ops_and_bytes(
                kernel, batch=batch, seq=rows,
                n_heads=config["num_attention_heads"],
                n_kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"],
                window=config["sliding_window_size"] if windowed else None)
            t, bound = counts.least_seconds(ops, nbytes,
                                            ctx["device"]["peaks"])
            print(f"  {kernel}, {batch} x {rows} rows, "
                  f"{'a window' if windowed else 'every key before'}: {n} "
                  f"calls, {1e3 * seconds / n:.3f} ms a call, least "
                  f"{1e3 * t:.3f} ms ({bound}-bound)")
            least += t * n
            spent += seconds
    return 100.0 * least / spent if spent else None
