"""Serving engine host loop (serve/engine.py): mean milliseconds from the
end of one decode step to the start of the next within a wave, from the
profiler trace.  The gap holds the engine's per-step host work (reading
the argmax back, Python per row, dispatch) and the small programs that
work launches; a pair with a prefill between it belongs to two waves and
is left out."""

import bisect


def step_gaps(execs, breakers):
    """Idle ns between consecutive (start, end) executions in ``execs``
    where no execution in ``breakers`` starts between them."""
    starts = sorted(s for s, _ in breakers)
    return [max(0, s1 - e0) for (_, e0), (s1, _) in zip(execs, execs[1:])
            if bisect.bisect_left(starts, e0) == bisect.bisect_left(starts, s1)]


def read(data):
    tr = data.get("trace")
    if not tr:
        return None
    decode, prefill = [], []
    for name, ex in tr["modules"].items():
        if "decode_step" in name:
            decode.extend(ex)
        elif "prefill" in name:
            prefill.extend(ex)
    gaps = step_gaps(sorted(decode), prefill)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
