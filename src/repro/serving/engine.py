"""Batched serving engine with Raptor flights over real jitted model stages.

Requests are grouped into batches; each invocation (prefill -> N decode
steps) is an ActionManifest executed by the Raptor engine.  With
``flight_size > 1`` the whole invocation is speculatively replicated across
executor groups (threads here; one process per model replica on a fleet),
with per-group latency jitter standing in for independent host/queue
variance — first finisher wins, peers are preempted (core.scheduler).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.manifest import ActionManifest, FunctionSpec
from repro.core.scheduler import Flight
from repro.models import transformer as tfm
from repro.serving.step import greedy_sample, make_decode_step, make_prefill_step


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 128
    decode_steps: int = 16
    flight_size: int = 1
    # per-group latency jitter model (independent "hosts"): exp(mean_jitter)
    mean_jitter_s: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.decode_steps < 1:
            raise ValueError(
                f"decode_steps must be >= 1, got {self.decode_steps}")
        if self.decode_steps >= self.max_len:
            raise ValueError(
                f"decode_steps={self.decode_steps} leaves no room for a "
                f"prompt inside max_len={self.max_len}")
        if self.flight_size < 1:
            raise ValueError(
                f"flight_size must be >= 1, got {self.flight_size}")
        if not self.mean_jitter_s >= 0.0:
            raise ValueError(
                f"mean_jitter_s must be >= 0, got {self.mean_jitter_s}")


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray              # [B, decode_steps]
    latency_s: float                # warm wall time of THIS call (no jit)
    flight_report: Optional[Any] = None
    cold_s: Optional[float] = None  # first-compile time, when this call
    #                                 triggered the warmup (else None)
    latencies_s: Optional[np.ndarray] = None   # per-request [B] latencies


@dataclasses.dataclass
class ServeStats:
    """Per-request latency accounting over a sequence of serve calls."""
    latencies_s: np.ndarray         # one entry per request (flattened)
    cold_s: float                   # first-call compile-inclusive time
    warm_s: float                   # post-warmup single-call reference

    @property
    def p50_s(self) -> float:
        return float(np.percentile(self.latencies_s, 50))

    @property
    def p99_s(self) -> float:
        return float(np.percentile(self.latencies_s, 99))

    def summary(self) -> dict:
        return {"requests": int(self.latencies_s.size),
                "mean_s": float(self.latencies_s.mean()),
                "p50_s": self.p50_s, "p99_s": self.p99_s,
                "cold_s": self.cold_s, "warm_s": self.warm_s}


def _prompt_len(batch: Dict[str, Any]) -> int:
    for name in ("tokens", "embeddings"):
        if name in batch:
            return int(batch[name].shape[1])
    raise ValueError("batch carries neither 'tokens' nor 'embeddings'")


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self._prefill = jax.jit(make_prefill_step(cfg, sc.max_len))
        self._decode = jax.jit(make_decode_step(cfg))
        self._rng = np.random.default_rng(sc.seed)
        self._warmed = set()        # batch signatures already compiled
        self.cold_s: Optional[float] = None   # first-compile wall time
        self.warm_s: Optional[float] = None   # warm reference (same shapes)

    def _check_budget(self, batch: Dict[str, Any]) -> None:
        p = _prompt_len(batch)
        if p + self.sc.decode_steps > self.sc.max_len:
            raise ValueError(
                f"prompt_len={p} + decode_steps={self.sc.decode_steps} "
                f"overflows the max_len={self.sc.max_len} cache budget")

    def _signature(self, batch: Dict[str, Any]):
        return tuple(sorted((k, tuple(v.shape)) for k, v in batch.items()))

    def warmup(self, batch: Dict[str, Any]) -> Dict[str, float]:
        """Compile prefill+decode for this batch shape; report cold/warm.

        Explicit so a service can pay jit before taking traffic; both
        ``generate`` paths call it lazily, so measured ``latency_s`` NEVER
        includes first-call compilation (the bug this replaces timed
        ``t0`` before the first jitted call).  Deterministic and
        rng-free — warmup cannot shift the jitter draw stream.
        """
        self._check_budget(batch)
        sig = self._signature(batch)
        if sig in self._warmed:
            return {"cold_s": 0.0, "warm_s": self.warm_s or 0.0}

        def once():
            logits, cache = self._prefill(self.params, batch)
            tok = greedy_sample(logits)[:, None]
            logits, _ = self._decode(self.params, cache, tok)
            jax.block_until_ready(logits)

        t0 = time.monotonic()
        once()
        cold = time.monotonic() - t0
        t0 = time.monotonic()
        once()
        warm = time.monotonic() - t0
        self._warmed.add(sig)
        if self.cold_s is None:
            self.cold_s, self.warm_s = cold, warm
        return {"cold_s": cold, "warm_s": warm}

    # ---- plain (stock) path ------------------------------------------
    def generate(self, batch: Dict[str, Any]) -> ServeResult:
        self._check_budget(batch)
        cold = None
        if self._signature(batch) not in self._warmed:
            cold = self.warmup(batch)["cold_s"]
        t0 = time.monotonic()
        logits, cache = self._prefill(self.params, batch)
        toks = []
        tok = greedy_sample(logits)[:, None]
        for _ in range(self.sc.decode_steps):
            toks.append(np.asarray(tok)[:, 0])
            logits, cache = self._decode(self.params, cache, tok)
            tok = greedy_sample(logits)[:, None]
        out = np.stack(toks, axis=1)
        dt = time.monotonic() - t0
        return ServeResult(out, dt, cold_s=cold,
                           latencies_s=np.full(out.shape[0], dt))

    # ---- Raptor flight path ------------------------------------------
    def generate_flight(self, batch: Dict[str, Any]) -> ServeResult:
        """Speculatively replicate the invocation across flight members."""
        self._check_budget(batch)
        cold = None
        if self._signature(batch) not in self._warmed:
            cold = self.warmup(batch)["cold_s"]
        sc = self.sc
        jitters = self._rng.exponential(
            max(sc.mean_jitter_s, 1e-9), size=(sc.flight_size, 2))

        def make_stage(stage: str):
            def fn(ctx):
                member = ctx.follower_index
                # independent host variance (queue/NIC/entropy analogue)
                if sc.mean_jitter_s:
                    ctx.sleep(float(jitters[member % sc.flight_size,
                                            0 if stage == "prefill" else 1]))
                if stage == "prefill":
                    logits, cache = self._prefill(self.params, batch)
                    return {"logits": np.asarray(logits), "cache": cache}
                pre = ctx.inputs["prefill"]
                cache = pre["cache"]
                tok = greedy_sample(jnp.asarray(pre["logits"]))[:, None]
                toks = []
                for _ in range(sc.decode_steps):
                    ctx.checkpoint()      # preemption point per decode step
                    toks.append(np.asarray(tok)[:, 0])
                    logits, cache = self._decode(self.params, cache, tok)
                    tok = greedy_sample(logits)[:, None]
                return np.stack(toks, axis=1)
            return fn

        manifest = ActionManifest((
            FunctionSpec("prefill", make_stage("prefill")),
            FunctionSpec("decode", make_stage("decode"),
                         dependencies=("prefill",)),
        ), concurrency=sc.flight_size, name="generate")
        t0 = time.monotonic()
        report = Flight(manifest).run(timeout=600.0)
        if not report.ok:
            raise RuntimeError("flight failed")
        dt = time.monotonic() - t0
        out = report.outputs["decode"]
        return ServeResult(out, dt, report, cold_s=cold,
                           latencies_s=np.full(out.shape[0], dt))

    def serve(self, batches, *, raptor: bool = None) -> ServeStats:
        """Serve a sequence of request batches; per-request latency stats.

        Warmup is paid once up front (first batch's shapes), so the
        returned latency distribution is pure serve time — cold/warm
        compile ride along separately in the stats.
        """
        batches = list(batches)
        if not batches:
            raise ValueError("serve needs at least one batch")
        if raptor is None:
            raptor = self.sc.flight_size > 1
        wu = self.warmup(batches[0])
        lat = []
        for b in batches:
            res = (self.generate_flight(b) if raptor else self.generate(b))
            lat.append(res.latencies_s)
        return ServeStats(np.concatenate(lat),
                          cold_s=(self.cold_s
                                  if self.cold_s is not None
                                  else wu["cold_s"]),
                          warm_s=self.warm_s or wu["warm_s"])


class SchedulerService:
    """Live Raptor *scheduling* service: open job arrivals booked on the
    streaming sim engine's persistent device-resident W-state.

    This is the service face of :class:`repro.sim.streaming.
    StreamingScheduler` — the launcher (``repro.launch.serve --mode
    scheduler``) and the ``queue_streaming`` bench tier drive sustained
    open load through it.  ``submit``/``drain`` mirror the engine;
    ``run_open_load`` is the batteries-included sustained driver.
    """

    def __init__(self, sim, *, microbatch: int = 64,
                 pipeline_depth: int = 2, seed: Optional[int] = None):
        from repro.sim.streaming import StreamingScheduler
        self.sim = sim
        self.microbatch = microbatch
        self.pipeline_depth = pipeline_depth
        self.seed = seed
        self.engine = StreamingScheduler(
            sim, microbatch=microbatch, pipeline_depth=pipeline_depth,
            seed=seed)

    @property
    def config(self):
        """The resolved (block, resolver, scan) the service books with."""
        return self.engine.config

    def submit(self, arrivals_ms) -> None:
        self.engine.submit(arrivals_ms)

    def drain(self):
        return self.engine.drain()

    def warmup(self) -> float:
        """Compile the service's executables on a scratch engine; returns
        the wall seconds (see :func:`repro.sim.streaming.warm_up`)."""
        from repro.sim.streaming import warm_up
        return warm_up(self.sim, microbatch=self.microbatch,
                       pipeline_depth=self.pipeline_depth, seed=self.seed)

    def run_open_load(self, **kw):
        """Sustained open load at this service's microbatch, pipeline
        depth and seed (keyword arguments override them)."""
        from repro.sim.streaming import run_open_load
        kw.setdefault("microbatch", self.microbatch)
        kw.setdefault("pipeline_depth", self.pipeline_depth)
        kw.setdefault("seed", self.seed)
        return run_open_load(self.sim, **kw)


def demo_requests(cfg: ModelConfig, batch: int, prompt_len: int, seed=0):
    rng = np.random.default_rng(seed)
    b: Dict[str, Any] = {}
    if cfg.embedding_inputs:
        b["embeddings"] = jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)),
            jnp.dtype(cfg.dtype)) * 0.02
    else:
        b["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    if cfg.is_encoder_decoder:
        b["enc_emb"] = jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)),
            jnp.dtype(cfg.dtype)) * 0.02
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(prompt_len)[None],
                               (batch, prompt_len))
        b["positions"] = jnp.broadcast_to(pos[None], (3, batch, prompt_len)
                                          ).astype(jnp.int32)
    return b
