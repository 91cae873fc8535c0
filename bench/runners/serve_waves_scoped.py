"""Runner of the ``serve_waves_scoped`` mixes: the ``serve_waves`` closed
loop, judged by the share of served tokens far off the reference, and in a
traced run the device time of the decode step under each of the program's
named scopes (``data["scopes"]``).

The check.  ``serve_waves`` judges by ``served_gap``, the widest gap by
which a served token's reference logit lies below the reference's best.  In
a mixture of experts a near-tie in the router lets bf16 rounding pick
another expert set than the float32 reference, and that token's gap can
reach the float8 control's widest (PERF.md, Open question 12): one flipped
token decides the widest gap.  So here the reading is ``off_token_share``,
the share of the sample's served positions whose gap exceeds
:data:`OFF_GAP` (a gap that is not a number counts as off), which a few
flipped tokens cannot carry and a fault that reaches most tokens does.
The widest gap is kept under ``detail``.

The trace's XLA-op events carry each operation's HLO instruction, not the
scope it was traced under.  The scope is in the compiled HLO: each
instruction's ``metadata={op_name="jit(decode_step)/.../mla/..."}``.  So
after the window the decode step is lowered again, as the engine jits it,
on the abstract parameters and cache, and compiled (a hit in the persistent
compilation cache); its instructions' names are matched to the ``%<name> =``
of the trace's op events inside ``jit_decode_step`` executions.  A scope's
time is the union of its operations' intervals (a loop and the operations
inside it overlap), per decode-step execution.  When fewer than
:data:`MATCHED` of those events name an instruction of the compiled text,
the two programs differ and no scope time is given.
"""

from __future__ import annotations

import bisect
import importlib.util
import re
from collections import defaultdict
from pathlib import Path

SCOPES = ("mla", "moe")
SUBSCOPES = {"moe": ("route", "experts", "shared")}
MATCHED = 0.95
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r"^%?([^\s=]+) = ")
_REFERENCE = re.compile(r"%([^\s,(){}=]+)")

_spec = importlib.util.spec_from_file_location(
    "bench_runner_serve_waves", Path(__file__).with_name("serve_waves.py"))
serve_waves = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_waves)

# A served token whose float32-reference logit lies more than this below the
# reference's best is off.  bf16 rounding alone stays under it (phi3-medium's
# widest gap reads at most 0.28); see PERF.md section 4 for the readings.
OFF_GAP = 0.5


def position_gaps(ref_logits, rows, choose=None):
    """Every served position's gap, in the order of ``rows``: this runner's
    copy of ``serve_waves`` takes it in place of ``_gaps``, which gives
    only the widest."""
    import numpy as np
    out = [np.zeros(0, np.float32)]
    for i, (row, start, toks) in enumerate(rows):
        lg = np.asarray(ref_logits[row, start:start + len(toks)], np.float32)
        pick = np.asarray(toks) if choose is None else choose[i]
        out.append(lg.max(-1) - lg[np.arange(len(toks)), pick])
    return np.concatenate(out)


serve_waves._gaps = position_gaps


def off_token_share(gaps) -> float:
    """The share of positions whose gap is not within :data:`OFF_GAP`."""
    import numpy as np
    gaps = np.asarray(gaps, np.float32)
    return float(np.mean(~(gaps <= OFF_GAP))) if gaps.size else 0.0


def scope_of(op_name: str) -> list[str]:
    """The scopes an op_name lies under: ``mla`` or ``moe``, and under
    ``moe`` also ``moe/<part>``."""
    parts = op_name.split("/")
    out = []
    for scope in SCOPES:
        if scope in parts:
            out.append(scope)
            nxt = parts[parts.index(scope) + 1:][:1]
            if nxt and nxt[0] in SUBSCOPES.get(scope, ()):
                out.append(f"{scope}/{nxt[0]}")
    return out


def instruction_scopes(hlo_text: str) -> dict[str, list[str]]:
    """Every instruction of a compiled HLO module's text, by name, with the
    scopes its op_name lies under (empty for none).

    An instruction the compiler made itself has no op_name of the program
    (one that starts ``jit(``): the TPU's grouped-matmul kernels read
    ``ragged-dot-none``, an async copy has none.  It takes the scopes that
    every instruction reading its result shares, so that the experts'
    matmuls count under ``moe/experts``."""
    own, users = {}, defaultdict(set)
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        own[m.group(1)] = (scope_of(op.group(1))
                           if op and op.group(1).startswith("jit(") else None)
        for ref in _REFERENCE.findall(line[m.end():]):
            users[ref].add(m.group(1))
    out: dict[str, list[str]] = {}

    def resolve(name):
        if name not in out:
            out[name] = []                    # a cycle resolves to nothing
            if own[name] is not None:
                out[name] = own[name]
            else:
                got = [resolve(u) for u in sorted(users[name]) if u in own]
                out[name] = [s for s in (got[0] if got else [])
                             if all(s in g for g in got)]
        return out[name]

    for name in own:
        resolve(name)
    return out


def scope_times(ops, executions, scopes_of: dict[str, list[str]],
                merge) -> dict | None:
    """Device ms per execution under each scope.  ``ops``: (event name,
    start_ns, end_ns) of one device's op events; ``executions``: (start_ns,
    end_ns) of the decode step; ``scopes_of``: :func:`instruction_scopes`;
    ``merge``: the union of intervals clipped to a window.  None when the
    events inside the executions do not name the compiled instructions."""
    execs = sorted(executions)
    if not execs:
        return None
    starts = [s for s, _ in execs]
    spans, seen, matched = defaultdict(list), 0, 0
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > execs[i][1]:
            continue                  # not inside a decode-step execution
        seen += 1
        m = _EVENT.match(name)
        if not m or m.group(1) not in scopes_of:
            continue
        matched += 1
        for scope in scopes_of[m.group(1)]:
            spans[scope].append((s, e))
    if not seen or matched < MATCHED * seen:
        return None
    lo, hi = execs[0][0], execs[-1][1]
    return {"decode_steps": len(execs), "ops": seen, "matched": matched,
            "ms_per_step": {k: sum(b - a for a, b in merge(v, lo, hi))
                            / len(execs) / 1e6
                            for k, v in sorted(spans.items())}}


def decode_hlo_text(run) -> str:
    """The compiled HLO text of the engine's decode step for this run's
    configuration and mix, lowered on abstract parameters and cache."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    from repro.serve.serve_step import make_decode_step
    cfg = run.model_config()
    b, t = int(run.mix["batch"]), int(run.mix["max_len"])
    step = jax.jit(make_decode_step(cfg, None), donate_argnums=(1,))
    return step.lower(tf.model_abstract_params(cfg),
                      tf.abstract_cache(cfg, b, t),
                      jax.ShapeDtypeStruct((b, 1), jnp.int32),
                      jax.ShapeDtypeStruct((), jnp.int32)
                      ).compile().as_text()


def decode_scopes(run) -> dict | None:
    tr = run.mod("trace_reduce")
    trace = tr.load(tr.find_profile(str(run.run_dir / "trace")))
    devs = trace.devices()
    if not devs:
        return None
    win = tr.window(trace)
    execs = [ex for name, exs in tr.modules(trace, win).items()
             if "decode_step" in name for ex in exs]
    return scope_times(trace.planes[devs[0]][tr.OPS_LINE], execs,
                       instruction_scopes(decode_hlo_text(run)), tr.merge)


def run(run) -> dict:
    import numpy as np
    out = serve_waves.run(run)
    gaps = out["checks"].pop("served_gap")
    out["checks"]["off_token_share"] = off_token_share(gaps)
    out["detail"]["served_gap"] = float(
        gaps[np.isfinite(gaps)].max(initial=0.0))
    control = out["control"]
    out["control"] = lambda: {
        "off_token_share": off_token_share(control()["served_gap"])}
    if run.trace:
        out["data"]["scopes"] = decode_scopes(run)
    return out
