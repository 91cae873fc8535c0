"""Runner of the ``serve_waves`` mixes: a closed loop of full waves through
``ServeEngine.generate``, with or without the live auditor.

The engine serves a list of requests in waves and never refills a slot, so
the loop hands it one full wave at a time and sends the next when it
returns: ``batch`` clients, each waiting for its reply.  A request's
latency runs from its wave's submission to the return of ``generate``,
which is when its caller gets its tokens.

Set-up serves one short wave at each length a wave of the mix is padded
to, which compiles every prefill and the decode step, and, with the
auditor on, runs the first full drift check of every request class the mix
reaches, which elects the class's golden into the run's own fleet store.  After the window, a sample
of the finished requests drawn from the seed, with the longest among them,
is run through the reference, and the auditor must report no failure and
no drift alarm.
"""

from __future__ import annotations

import time


def _classes(mix: dict, cycle) -> set:
    """The request classes a cycle of the mix reaches."""
    from repro.audit.classes import classify
    b = int(mix["batch"])
    out = set()
    for wv in cycle:
        out.add(classify("prefill", b, wv.padded_len))
        for pos in range(wv.padded_len, wv.padded_len + max(wv.output_lens)
                         - 1):
            out.add(classify("decode", b, pos))
    return out


def _gaps(ref_logits, rows, choose=None):
    """Per request, the widest gap by which the chosen token's reference
    logit lies below the reference's best, over its served positions.
    ``rows`` holds (row, first position, tokens); ``choose`` gives the
    chosen token per position (default: the served tokens)."""
    import numpy as np
    worst = 0.0
    for i, (row, start, toks) in enumerate(rows):
        lg = np.asarray(ref_logits[row, start:start + len(toks)], np.float32)
        pick = np.asarray(toks) if choose is None else choose[i]
        gap = lg.max(-1) - lg[np.arange(len(toks)), pick]
        worst = max(worst, float(gap.max()))
    return worst


def check_sample(run, requests):
    """The requests the correctness check reads: ``check_requests`` of
    them drawn from the seed, the longest served always among them."""
    rng = run.mod("traffic").rng_for(run.seed, 4)
    longest = max(range(len(requests)),
                  key=lambda i: (len(requests[i].generated), -i))
    rest = [i for i in range(len(requests)) if i != longest]
    k = min(len(rest), int(run.mix["check_requests"]) - 1)
    picked = [longest] + [int(i) for i in rng.choice(rest, k, replace=False)]
    return [requests[i] for i in sorted(picked)]


def reference_inputs(requests):
    """Right-padded (N, T) prompts with their served tokens, and per request
    (row, position of its first served logit, served tokens)."""
    import numpy as np
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
            for r in requests]
    t = -(-max(len(s) for s in seqs) // 128) * 128
    tokens = np.zeros((len(seqs), t), np.int32)
    rows = []
    for i, (r, s) in enumerate(zip(requests, seqs)):
        tokens[i, :len(s)] = s
        rows.append((i, len(r.prompt) - 1, list(r.generated)))
    return tokens, rows


def run(run) -> dict:
    import jax
    import numpy as np
    from repro.serve.engine import EngineConfig, Request, ServeEngine

    traffic, e2e = run.mod("traffic"), run.mod("e2e")
    mix, shape = run.mix, run.shape
    cfg = run.model_config()
    params = run.make_params(cfg)
    aud = mix.get("auditor")
    ecfg = EngineConfig(batch_size=int(mix["batch"]),
                        max_len=int(mix["max_len"]),
                        audit_timeout_s=600.0)
    if aud:
        ecfg.store = str(run.run_dir / "store")
        ecfg.engine_id = f"bench-{run.name}"
        ecfg.audit_sample_every = int(aud["sample_every"])
        ecfg.audit_recheck_every = int(aud["recheck_every"])
    engine = ServeEngine(cfg, params, ecfg=ecfg)
    cycle = traffic.wave_cycle(mix)
    b = int(mix["batch"])
    ann = jax.profiler.TraceAnnotation

    # set-up: every prefill length and the decode step, then each class's
    # first drift check
    warm_rng = traffic.rng_for(run.seed, 5)
    for p in sorted({wv.padded_len for wv in cycle}):
        engine.generate([Request(rid=-1 - i, prompt=warm_rng.integers(
            0, shape["vocab"], p, dtype=np.int32), max_new_tokens=2)
            for i in range(b)])
    if engine.auditor is not None:
        for rc in sorted(_classes(mix, cycle)):
            engine.auditor.sample(rc, "every_n")
        engine.auditor.flush()

    def checks_run():
        log = engine.auditor.log if engine.auditor else None
        return 0 if log is None else sum(
            c.get("check", 0) + c.get("alarm", 0) for c in log.counts.values())

    stream = traffic.wave_stream(mix, run.seed, shape["vocab"])
    served, latency, waves_s, checks0 = [], [], [], checks_run()
    rid = 0
    with run.window():
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < run.window_seconds:
            _, prompts, lens = next(stream)
            wave = []
            for row, n in zip(prompts, lens):
                wave.append(Request(rid=rid, prompt=row, max_new_tokens=n))
                rid += 1
            t0 = time.perf_counter()
            with ann("bench.serve.wave"):
                engine.generate(wave)
            dt = time.perf_counter() - t0
            served.extend(wave)
            latency.extend([dt] * len(wave))
            waves_s.append(dt)
    run.read_memory()
    audit_checks = checks_run() - checks0

    tokens = sum(len(r.generated) for r in served)
    short = sum(len(r.generated) != r.max_new_tokens for r in served)
    checks = {"short_requests": short}
    if engine.auditor is not None:
        s, log = engine.stats, engine.auditor.log
        errors = sum(c.get("error", 0) for c in log.counts.values())
        checks["audit_failures"] = (s["audit_failures"] + s["audit_timeouts"]
                                    + s["audit_skipped"] + errors)
        checks["drift_alarms"] = s["audit_alarms"]
    model_flops = sum(run.family.served_request(shape, len(r.prompt),
                                                len(r.generated))
                      for r in served)

    # the program's state goes before the reference runs
    sample = check_sample(run, served)
    del engine
    tokens_in, rows = reference_inputs(sample)
    ref = run.reference("f32").logits(params, tokens_in)
    checks["served_gap"] = _gaps(ref, rows)

    return {"attempted": len(served), "failed": short,
            "e2e": {"serve_tokens_per_s": e2e.per_second(tokens,
                                                         run.window_s),
                    "request_p95_s": e2e.p95(latency)},
            "data": {"requests": len(served), "model_flops": model_flops,
                     "audit_checks": audit_checks},
            "checks": checks,
            "detail": {"wave_s": waves_s},
            "control": lambda: {"served_gap": control_gap(run, params,
                                                          sample)}}


def control_gap(run, params, sample) -> float:
    """The control's reading of ``served_gap`` on ``sample``: at each served
    position the float8 reference's first choice, judged by the float32
    reference."""
    import numpy as np
    tokens_in, rows = reference_inputs(sample)
    ref = run.reference("f32").logits(params, tokens_in)
    low = np.asarray(run.reference("fp8").logits(params, tokens_in))
    choose = [low[row, start:start + len(toks)].argmax(-1)
              for row, start, toks in rows]
    return _gaps(ref, rows, choose)
