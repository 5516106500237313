"""Share of their roofline that the expert layer's grouped matmuls
reached, in percent: the least time the chip could take for the grouped
products the trace shows inside the runs of `program` on the first
device (forward, recomputed and backward alike; each costs what
`grouped_matmul_ops_and_bytes` of `benchmark/flops/<flops>.py` says of
the cell's configuration, on the peaks of `benchmark/peaks.json`: the
count is the kernel's, whichever family runs it), over their device time
and that of every other op under `scope`, the SwiGLU's elementwise
passes between them. An op is a grouped product if it is one of
`products` by its name in the trace without the compiler's numbering
(`ragged-dot-none.3`, `gmm.1`) or by the primitive its scope path ends
in: the kernel the TPU compiler makes of `lax.ragged_dot` carries no
scope path, only its own name. Prints which bound holds. A program
without such ops gives None."""

import bisect

from benchmark.harness import spans as sp
from benchmark.harness import trace
from benchmark.harness.manifest import plugin


def products_seen(events, names, program, scope, products):
    """(grouped products run, seconds of them and of every other op
    under `scope`) inside the runs of `program` on the first device."""
    products = set(products)
    calls, seconds = 0, 0.0
    for dev in trace._first(events):
        mods = sorted((m for m in dev["modules"]
                       if trace.program_name(m[0]) == program),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, start, self_ns, _ in trace._self_times(dev["ops"]):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= mods[i][1] + mods[i][2]:
                continue
            path = names.get((sp.program_id(mods[i][0]), name)) or ""
            leaf = path.rstrip(":").rsplit("/", 1)[-1]
            product = leaf in products or trace.op_class(name) in products
            if product or scope in sp.scope_tokens(path):
                seconds += self_ns / 1e9
                calls += product
    return calls, seconds


def read(ctx, program, scope, products, flops):
    if ctx["trace"] is None:
        return None
    config, run = ctx["cell"].config, ctx["run"]
    counts = plugin("flops", flops)
    calls, seconds = products_seen(
        ctx["trace"], sp.op_names(sp.xplane_path(ctx)), program, scope,
        products)
    if not calls:
        return None
    ops, nbytes = counts.grouped_matmul_ops_and_bytes(
        config, run["batch"] // ctx["device"]["count"] * run["seq"])
    t, bound = counts.least_seconds(ops, nbytes, ctx["device"]["peaks"])
    print(f"  {scope}: {calls} grouped products, least {1e3 * t:.3f} ms "
          f"each ({bound}-bound), {1e3 * seconds / calls:.3f} ms of device "
          f"time a product with what runs between them")
    return 100.0 * t * calls / seconds
