"""Model step (serve/serve_step.py): device milliseconds per execution of
the jitted decode step, from the profiler trace's executables."""


def read(data):
    tr = data.get("trace")
    if not tr:
        return None
    runs = [e - s for name, ex in tr["modules"].items()
            if "decode_step" in name for s, e in ex]
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6
