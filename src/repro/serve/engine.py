"""Batched serving engine: continuous batching over prefill/decode steps.

A minimal but real engine: requests enter a queue, are prefilled in batches,
then decoded together with a shared step counter.  Slot management keeps the
decode batch full (continuous batching); finished sequences free their slot
for the next queued request.  The engine exposes an optional Magneton energy
audit of its prompt pass (``energy_report()``) — the paper's profiler as a
deployment feature.

The audit path sits behind an error boundary (:meth:`ServeEngine.audit`):
a watchdog thread bounds how long an audit may run, every failure is
absorbed into ``stats`` counters, and a circuit breaker disables further
audits after ``audit_breaker_threshold`` consecutive failures — a broken
profiler must never take the serving path down with it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.audit import AuditConfig, EngineAuditor, RequestClass
from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import transformer as tf
from repro.serve.serve_step import make_decode_step, make_prefill_step


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class EngineConfig:
    batch_size: int = 4             # decode slots
    max_len: int = 256
    eos_id: int = -1                # -1: never stop early
    attn_impl: str = "xla"
    # audit error boundary (docs/robustness.md): wall-clock budget for one
    # energy audit, and how many consecutive failures open the breaker
    audit_timeout_s: float = 120.0
    audit_breaker_threshold: int = 3
    # always-on sampled auditing (docs/serving.md).  A sampler trigger must
    # be set (cadence and/or SLO headroom) for live audits to run; the
    # store URI makes captures/goldens/logs land in a shared fleet store.
    audit_sample_every: int = 0      # every-Nth cadence per class (0 = off)
    audit_slo_ms: float | None = None
    audit_slo_headroom: float = 0.5
    store: str | None = None         # fleet store URI (file:// or http(s)://)
    engine_id: str | None = None     # None: derived from arch + pid
    audit_seed: int = 0
    audit_recheck_every: int = 0     # full drift re-check cadence (0 = once)
    audit_energy_rtol: float = 0.05
    # demo/chaos hook: audit the decode probe through a waste mutation
    # (repro.testing.mutate name) — simulates a regressed engine that must
    # alarm against the healthy fleet golden
    audit_mutate_decode: str | None = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, mesh: Mesh | None = None,
                 ecfg: EngineConfig | None = None):
        assert cfg.is_causal, "encoder-only models have no decode path"
        # None default: a shared `ecfg=EngineConfig()` dataclass default
        # would alias one mutable config across every engine construction
        self.ecfg = ecfg if ecfg is not None else EngineConfig()
        # every served step (prefill included) runs attention over a cache
        if self.ecfg.attn_impl not in attn_mod.CACHED_ATTN_IMPLS:
            raise ValueError(
                f"attn_impl={self.ecfg.attn_impl!r} cannot serve: prefill "
                f"and decode attend over a KV cache, which only "
                f"{attn_mod.CACHED_ATTN_IMPLS} implement")
        if mesh is not None:
            params = jax.device_put(params,
                                    tf.model_param_shardings(cfg, mesh))
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        # raw (traceable) prefill kept alongside the jitted one: the live
        # audit probe captures through it so Magneton sees real operators
        self._prefill_fn = make_prefill_step(
            cfg, mesh, max_len=self.ecfg.max_len,
            attn_impl=self.ecfg.attn_impl)
        self._prefill = jax.jit(self._prefill_fn)
        self._decode = jax.jit(make_decode_step(cfg, mesh,
                                                attn_impl=self.ecfg.attn_impl))
        self.stats = {"prefill_calls": 0, "decode_calls": 0,
                      "tokens_generated": 0, "prefill_s": 0.0, "decode_s": 0.0,
                      # audit-health counters (the audit error boundary)
                      "audit_calls": 0, "audit_ok": 0, "audit_failures": 0,
                      "audit_timeouts": 0, "audit_skipped": 0,
                      "audit_degraded": 0, "audit_consecutive_failures": 0,
                      "audit_breaker_open": False,
                      # live sampled auditing (repro.audit)
                      "audit_sampled": 0, "audit_alarms": 0}
        ecfg = self.ecfg
        self.engine_id = ecfg.engine_id or f"{cfg.name}-{os.getpid()}"
        self.auditor: EngineAuditor | None = None
        if (ecfg.audit_sample_every > 0 or ecfg.audit_slo_ms is not None
                or ecfg.store is not None):
            self.auditor = EngineAuditor(
                self._audit_probe, self._audit_fingerprint(),
                AuditConfig(engine_id=self.engine_id, store=ecfg.store,
                            sample_every=ecfg.audit_sample_every,
                            slo_ms=ecfg.audit_slo_ms,
                            slo_headroom=ecfg.audit_slo_headroom,
                            seed=ecfg.audit_seed,
                            energy_rtol=ecfg.audit_energy_rtol,
                            recheck_every=ecfg.audit_recheck_every))

    # -- batch serving --------------------------------------------------------
    def generate(self, requests: list[Request]) -> list[Request]:
        """Serve a list of requests with continuous batching."""
        ecfg = self.ecfg
        queue = list(requests)
        B = min(ecfg.batch_size, len(queue))
        if B == 0:
            return requests

        # pad all prompts in one prefill batch per wave
        waves = [queue[i:i + B] for i in range(0, len(queue), B)]
        for wave in waves:
            self._serve_wave(wave)
        if self.auditor is not None:
            self.auditor.flush()        # deliver batched capture events
        return requests

    def _serve_wave(self, wave: list[Request]):
        ecfg = self.ecfg
        B = len(wave)
        plen = max(len(r.prompt) for r in wave)
        tokens = np.zeros((B, plen), np.int32)
        for i, r in enumerate(wave):
            tokens[i, plen - len(r.prompt):] = r.prompt    # left-pad
        t0 = time.time()
        img = None
        if self.cfg.family == "vlm":
            img = jnp.zeros((B, self.cfg.num_image_tokens, self.cfg.d_model),
                            jnp.dtype(self.cfg.dtype))
        logits, caches = self._prefill(self.params, jnp.asarray(tokens), img)
        dt = time.time() - t0
        self.stats["prefill_calls"] += 1
        self.stats["prefill_s"] += dt
        self._observe_audit("prefill", B, plen, latency_s=dt)

        next_tok = np.asarray(jnp.argmax(logits[:, -1, :], -1),
                              np.int32)[:, None]
        for i, r in enumerate(wave):
            r.generated.append(int(next_tok[i, 0]))

        pos = plen
        max_new = max(r.max_new_tokens for r in wave)
        for _ in range(max_new - 1):
            if pos >= ecfg.max_len:
                break
            t0 = time.time()
            logits, caches = self._decode(self.params, caches,
                                          jnp.asarray(next_tok),
                                          jnp.int32(pos))
            dt = time.time() - t0
            self.stats["decode_calls"] += 1
            self.stats["decode_s"] += dt
            self._observe_audit("decode", B, pos, latency_s=dt)
            next_tok = np.asarray(jnp.argmax(logits[:, -1, :], -1),
                                  np.int32)[:, None]
            pos += 1
            for i, r in enumerate(wave):
                if r.done or len(r.generated) >= r.max_new_tokens:
                    r.done = True
                    continue
                t = int(next_tok[i, 0])
                r.generated.append(t)
                if t == ecfg.eos_id:
                    r.done = True
            if all(r.done or len(r.generated) >= r.max_new_tokens
                   for r in wave):
                break
        self.stats["tokens_generated"] += sum(len(r.generated) for r in wave)

    # -- Magneton audit --------------------------------------------------------
    def energy_report(self, *, prompt_len: int = 32, session=None):
        """Differential energy audit of this engine's prompt pass against
        its all-position-logits wasteful twin (hf-38977) — the profiler as
        a serving feature.

        Both sides are cache-free forward passes of the engine's model and
        weights over the same ``(batch_size, prompt_len)`` prompt, not the
        served prefill step (which also writes the KV cache).  The
        efficient side computes the LM head for the last position only;
        the twin returns every position's logits from the model, as a
        model that hands them to its sampler does, and keeps the last.
        The one difference is the (prompt_len - 1) x vocab redundant
        logits.  ``optimization_barrier`` marks where the twin's model
        call ends: without it XLA slices before the LM-head dot and
        compiles the twin to the efficient program, leaving nothing for an
        HLO-priced audit to find.

        Runs on the Session/artifact API: pass a store-backed
        :class:`repro.core.session.Session` to persist the captures and
        make repeated audits of an unchanged engine cache hits.
        """
        from repro.core.session import Session
        cfg = self.cfg
        B = self.ecfg.batch_size
        key = jax.random.key(0)
        tokens = jax.random.randint(key, (B, prompt_len), 0, cfg.vocab_size)
        img = None
        if cfg.family == "vlm":
            img = jnp.zeros((B, cfg.num_image_tokens, cfg.d_model),
                            jnp.dtype(cfg.dtype))

        def logits(toks, mode):
            out, _ = tf.forward(cfg, self.params, toks, image_embeds=img,
                                remat=False, attn_impl=self.ecfg.attn_impl,
                                logits_mode=mode)
            if mode == "all":
                out = jax.lax.optimization_barrier(out)
            return out[:, -1:].astype(jnp.float32)

        session = session or Session()
        art_waste = session.capture(lambda t: logits(t, "all"), (tokens,),
                                    name="lmhead-all")
        art_eff = session.capture(lambda t: logits(t, "last"), (tokens,),
                                  name="lmhead-last")
        return session.compare(art_waste, art_eff)

    def audit(self, *, prompt_len: int = 32, session=None,
              timeout_s: float | None = None):
        """Error-bounded :meth:`energy_report`: never raises, never hangs.

        Runs the audit on a watchdog daemon thread with a wall-clock budget
        (``timeout_s``, default ``ecfg.audit_timeout_s``).  Returns the
        :class:`~repro.core.report.Report` on success, ``None`` on any
        failure/timeout/open-breaker — serving always continues.  Health is
        tracked in ``stats``: after ``ecfg.audit_breaker_threshold``
        consecutive failures the circuit breaker opens and later calls are
        counted as ``audit_skipped`` without running anything, until
        :meth:`reset_audit_breaker`.
        """
        if self.stats["audit_breaker_open"]:
            self.stats["audit_skipped"] += 1
            return None
        return self._bounded_audit(
            lambda: self.energy_report(prompt_len=prompt_len,
                                       session=session),
            timeout_s=timeout_s)

    def _bounded_audit(self, thunk: Callable[[], Any], *,
                       timeout_s: float | None = None):
        """The shared watchdog/breaker boundary: run one audit thunk (an
        energy report or a sampled live audit) with a wall-clock budget,
        absorbing every failure into the health counters."""
        self.stats["audit_calls"] += 1
        budget = timeout_s if timeout_s is not None \
            else self.ecfg.audit_timeout_s
        box: dict[str, Any] = {}

        def run():
            try:
                box["result"] = thunk()
            except BaseException as e:        # incl. SimulatedCrash in tests
                box["error"] = e

        # daemon watchdog: a hung audit (dead store mount, wedged compile)
        # is abandoned at the deadline and must not block shutdown either
        t = threading.Thread(target=run, name="magneton-audit", daemon=True)
        t.start()
        t.join(budget)
        if t.is_alive():
            self.stats["audit_timeouts"] += 1
            self._audit_failed(f"audit exceeded {budget:g}s watchdog budget")
            return None
        if "error" in box:
            self._audit_failed(f"{type(box['error']).__name__}: "
                               f"{box['error']}")
            return None
        result = box.get("result")
        self.stats["audit_ok"] += 1
        self.stats["audit_consecutive_failures"] = 0
        if result is not None and (getattr(result, "is_degraded", False)
                                   or getattr(result, "degraded", False)):
            self.stats["audit_degraded"] += 1
        return result

    def _audit_failed(self, reason: str) -> None:
        self.stats["audit_failures"] += 1
        self.stats["audit_consecutive_failures"] += 1
        self.stats["audit_last_error"] = reason
        if (self.stats["audit_consecutive_failures"]
                >= self.ecfg.audit_breaker_threshold):
            self.stats["audit_breaker_open"] = True

    def reset_audit_breaker(self) -> None:
        """Re-arm auditing after the underlying fault has been fixed."""
        self.stats["audit_breaker_open"] = False
        self.stats["audit_consecutive_failures"] = 0

    # -- always-on sampled auditing (repro.audit, docs/serving.md) ------------
    def _audit_fingerprint(self) -> str:
        """Identity of the audited configuration: model + engine knobs.

        The demo decode mutation (``audit_mutate_decode``) is deliberately
        NOT part of it: a mutated engine must compare against the healthy
        fleet golden and alarm — not elect a golden of its own.
        """
        ident = {"arch": self.cfg.name, "batch_size": self.ecfg.batch_size,
                 "max_len": self.ecfg.max_len,
                 "attn_impl": self.ecfg.attn_impl}
        return hashlib.sha256(
            json.dumps(ident, sort_keys=True).encode()).hexdigest()[:16]

    def _audit_probe(self, rc: RequestClass):
        """Canonical seeded probe for one request class: ``(fn, args,
        config)`` for ``Session.capture``.

        The probe inputs are derived from the class key alone, so every
        engine in a fleet captures the same content-addressed artifact for
        the same class under the same config — the property golden sharing
        and conditional-put convergence rest on.
        """
        cfg = self.cfg
        seed = int.from_bytes(
            hashlib.sha256(rc.key.encode()).digest()[:4], "big")
        rng = np.random.default_rng(seed)
        B = max(1, min(rc.probe_batch, self.ecfg.batch_size))
        L = max(1, min(rc.probe_seq_len, self.ecfg.max_len - 1))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(B, L)), jnp.int32)
        img = None
        if cfg.family == "vlm":
            img = jnp.zeros((B, cfg.num_image_tokens, cfg.d_model),
                            jnp.dtype(cfg.dtype))
        config = {"class": rc.key, "arch": cfg.name,
                  "attn_impl": self.ecfg.attn_impl}

        if rc.phase == "prefill":
            def prefill_probe(toks):
                logits, _ = self._prefill_fn(self.params, toks, img)
                return logits.astype(jnp.float32)
            return prefill_probe, (tokens,), config

        _, caches = self._prefill(self.params, tokens, img)

        def decode_probe(tok):
            logits, _ = tf.decode_step(cfg, self.params, caches, tok,
                                       jnp.int32(L))
            return logits.astype(jnp.float32)

        fn = decode_probe
        tok = jnp.zeros((B, 1), jnp.int32)
        if self.ecfg.audit_mutate_decode:
            from repro.testing.mutate import MUTATIONS, make_mutant
            mutation = MUTATIONS[self.ecfg.audit_mutate_decode]()
            fn, _sites = make_mutant(decode_probe, mutation, (tok,),
                                     name=f"decode__{mutation.name}")
        return fn, (tok,), config

    def _observe_audit(self, phase: str, batch: int, seq_len: int, *,
                       latency_s: float | None = None) -> None:
        """Feed one engine step to the sampler; run a sampled audit through
        the watchdog/breaker boundary when the policy fires."""
        if self.auditor is None:
            return
        rc, dec = self.auditor.observe(phase, batch, seq_len,
                                       latency_s=latency_s)
        if not dec.sample:
            return
        if self.stats["audit_breaker_open"]:
            self.stats["audit_skipped"] += 1
            return
        self.stats["audit_sampled"] += 1
        self._bounded_audit(
            lambda: self.auditor.sample(rc, dec.reason, latency_s=latency_s))
        self.stats["audit_alarms"] = self.auditor.log.alarm_count()

    def health(self) -> dict[str, Any]:
        """JSON-serializable service health: engine identity, the audit
        error-boundary state, and the live-audit summary.  Round-trips
        through ``json.dumps``/``json.loads`` unchanged — it is what a
        ``/healthz`` endpoint or the fleet dashboard would serve."""
        return {"engine_id": self.engine_id,
                "arch": self.cfg.name,
                "batch_size": self.ecfg.batch_size,
                "max_len": self.ecfg.max_len,
                "attn_impl": self.ecfg.attn_impl,
                "store": self.ecfg.store,
                "audit_breaker_open": self.stats["audit_breaker_open"],
                "audit_last_error": self.stats.get("audit_last_error"),
                "stats": dict(self.stats),
                "audit": (self.auditor.summary()
                          if self.auditor is not None else None)}
