"""Of the records of two of the program's stages in the (untraced)
window, the share that carry the first name, in percent: how many of
`stage` over how many of `stage` and `beside` together (`critical_path`,
read from the flight recorder's ring with the profiler off). For two
names that say one thing apart by kind, such as the engine's block gaps
behind a wave and without one. A window that lacks records of either
name gives None, not 0 or 100: a program that records one of them
alone (or, as the parent of the PR that adds them, neither) is not
read as one that had none of the other kind."""


def read(ctx, stage, beside):
    stages = ctx["run"].get("stages", {})
    n, m = len(stages.get(stage, ())), len(stages.get(beside, ()))
    if not n or not m:
        return None
    print(f"  {stage}: {n} records beside {m} of {beside}")
    return 100.0 * n / (n + m)
