"""90th percentile, over all requests issued in the window, of first
``on_token`` minus the instant the client issued the request (a failed or
refused request counts as 1e9 s). A closed loop of 16 callers issues some 35
requests in a window, too few for a tail that could judge a PR: it stands
here, beside the end-to-end metrics, and is not bounded. The open-loop cell
of PERF.md, section 7, is to own the end-to-end time to first token."""
import numpy as np


def read(ctx):
    if not ctx.on_chip:
        return None
    t = ctx.facts["ttft"]
    return float(np.percentile(t, 90)) if len(t) else None
