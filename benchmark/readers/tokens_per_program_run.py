"""Tokens that reached the client during the traced stretch over the
runs of one device program in the trace: for the decode program, how
many of its slots a step really filled."""

from benchmark.harness import trace, traffic


def read(ctx, program):
    if ctx["trace"] is None:
        return None
    runs = trace.program_runs(ctx["trace"]).get(program)
    if not runs:
        return None
    tokens = traffic.tokens_in_window(
        ctx["run"]["records"], ctx["run"]["trace_t0"], ctx["run"]["trace_t1"])
    return tokens / len(runs)
