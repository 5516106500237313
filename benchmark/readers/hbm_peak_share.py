"""Peak device memory over what the backend offers, in percent, on the
fullest chip: live arrays at their peak plus the largest reservation a
running program made for its temporaries."""


def read(ctx):
    shares = [
        100.0 * ((m["peak_bytes_in_use"] or 0)
                 + (m["peak_bytes_reserved"] or 0)) / m["bytes_limit"]
        for m in ctx["run"]["memory"] if m.get("bytes_limit")]
    return max(shares) if shares else None
