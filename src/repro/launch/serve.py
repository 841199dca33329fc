"""Serving driver: prefill + batched decode with sharded KV caches, and the
Pegasus LUT path as a first-class serving feature (--pegasus).

``serve_step`` is the unit the decode_32k/long_500k dry-run cells lower:
one new token for the whole batch against preallocated caches/states.

``PegasusServer`` is the dataplane-model analog for ONE model: a compiled
:class:`repro.engine.ExecutionPlan` (layouts + int8 LUTs precomputed at
plan-build) reused across every request batch, with the backend —
``gather | onehot | kernel | kernel_q8`` — chosen once via ``--backend``.

``MultiModelServer`` is the scale step the paper's pitch implies (a shared
dataplane serves MANY models and traffic classes at once — Quark runs whole
CNNs on one switch, FENIX multiplexes DNN workloads through one pipeline):
N named heterogeneous plans (MLP/RNN/CNN/AE) behind one server, requests
addressed ``(model_name, inputs)``, same-model requests coalesced into
bucket-aligned micro-batches, models scheduled by weighted fair queueing
(:class:`repro.launch.scheduler.WFQScheduler`: deficit round-robin over
priority-weighted queues), and per-model serving + compile-cache +
latency stats.

``AsyncMultiModelServer`` makes that an always-on service: a background
drain thread, thread-safe ``submit()`` returning futures, an
asyncio-native ``infer_async()`` frontend, and bounded per-model queues
with reject/block backpressure — the host-side analog of FENIX's
multiplexed pipeline under continuous ingestion.

Requests may carry a ``deadline_ms`` budget: the scheduler sheds a
request whose queue-wait has already burned through its slack instead of
dispatching it (its future fails with
:class:`~repro.launch.scheduler.DeadlineExceededError`; sync ``serve()``
surfaces sheds through :class:`PartialDrainError`), and admission control
refuses doomed requests at submit once a service rate is observed. See
docs/SERVING.md for the operator guide.
"""

from __future__ import annotations

import argparse
import asyncio
import threading
import time
import warnings
from collections import deque
from concurrent.futures import Future

import concurrent.futures
import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.sanitizer import ThreadAffinity, make_lock
from repro.configs.registry import ArchConfig, get_config, smoke_config
from repro.models.transformer import (
    decode_step, forward_train, init_decode_state, init_model,
)

from .compile_cache import enable_compile_cache
from .devices import DeviceStreamPool
from .health import CLOSED, CircuitBreaker
from .mesh import batch_specs, decode_state_specs, named, param_specs
from .request import InferRequest, InferResult
from .scheduler import (
    PRIORITY_WEIGHTS, DeadlineExceededError, QueueFullError, WFQScheduler,
)

__all__ = ["make_serve_step", "make_prefill_step", "Server", "PegasusServer",
           "MultiModelServer", "AsyncMultiModelServer", "PartialDrainError",
           "QueueFullError", "DeadlineExceededError", "PRIORITY_WEIGHTS",
           "InferRequest", "InferResult", "DeviceStreamPool",
           "ServerStoppedError", "PoisonedRequestError", "FALLBACK_BACKEND",
           "mlp_b_server"]

# The bottom rung of the backend fallback ladder: plain jnp gather — no
# Pallas kernel, no one-hot matmul structure, the least machinery that can
# possibly fail. A model whose preferred-backend path trips its breaker
# keeps serving on a gather plan (degraded) until a probe back on the
# preferred path succeeds.
FALLBACK_BACKEND = "gather"


def _warn_legacy(what: str, instead: str) -> None:
    """One DeprecationWarning per call site (the default filter dedupes by
    location) for the pre-typed call shapes — kept as working shims."""
    warnings.warn(
        f"{what} is deprecated; {instead} (see repro.launch.request)",
        DeprecationWarning, stacklevel=3)


def _as_requests(requests, *, named: bool) -> tuple[list, bool]:
    """Normalize a ``serve()`` argument into ``(list[InferRequest], typed)``.

    Typed calls pass :class:`InferRequest` items through unchanged. Legacy
    items — bare arrays / input tuples when ``named=False``
    (``PegasusServer``), ``(name, inputs[, deadline_ms])`` triples when
    ``named=True`` (``MultiModelServer``) — are wrapped; the caller emits
    the deprecation warning. Mixing the two shapes in one list is a
    ``TypeError`` (the return type would be ambiguous)."""
    items = list(requests)
    if not items:
        return [], True
    n_typed = sum(isinstance(r, InferRequest) for r in items)
    if n_typed == len(items):
        return items, True
    if n_typed:
        raise TypeError(
            "serve() got a mix of InferRequest and legacy-shaped items — "
            "pass one or the other, not both")
    out = []
    for item in items:
        if named:
            name, inputs = item[0], item[1]
            deadline_ms = item[2] if len(item) > 2 else None
            out.append(InferRequest(name, inputs, deadline_ms=deadline_ms))
        else:   # the item IS the inputs (single array or input tuple)
            inputs = tuple(item) if isinstance(item, (tuple, list)) else item
            out.append(InferRequest("", inputs))
    return out, False


class PartialDrainError(RuntimeError):
    """Some requests did not serve — a model failed to drain and/or
    deadline-bearing requests were shed — while the rest completed.

    Raised by :meth:`MultiModelServer.serve` instead of mutating and
    re-raising the underlying exception (the old ``err.partial_results =
    ...`` decoration failed with ``AttributeError`` on slotted/immutable
    exception types and permanently decorated an exception object that may
    be shared or re-raised elsewhere). Carries:

      * ``partial_results`` — ``{name: [outputs]}`` for every model that DID
        serve (that work is computed and counted; only the failed models'
        requests need resubmitting). A failed model that served SOME slices
        before failing appears here too, with its served prefix — its name
        in ``failed`` is what marks it incomplete,
      * ``failed`` — ``{name: exception}`` for every requested model that
        did not,
      * ``shed`` — ``{name: [DeadlineExceededError per shed request]}``
        for requests dropped for a missed deadline (refused at admission
        or shed at pull time). Shed work was never computed — resubmit it
        only if the caller still wants a LATE answer, and
      * ``__cause__`` — the first underlying exception (``raise ... from``).
    """

    def __init__(self, failed: dict, partial_results: dict,
                 shed: dict | None = None):
        self.failed = dict(failed)
        self.partial_results = partial_results
        self.shed = {k: list(v) for k, v in (shed or {}).items()}
        parts = []
        if self.failed:
            names = ", ".join(sorted(self.failed))
            parts.append(f"model(s) {names} failed to drain: "
                         f"{next(iter(self.failed.values()))!r}")
        if self.shed:
            n = sum(len(v) for v in self.shed.values())
            parts.append(f"{n} request(s) shed past their deadline on "
                         f"{', '.join(sorted(self.shed))}")
        super().__init__(
            "; ".join(parts) + " (served models' outputs are in "
            ".partial_results; per-model errors in .failed; shed requests "
            "in .shed)")


class ServerStoppedError(RuntimeError):
    """The server was stopped with this request still queued
    (``AsyncMultiModelServer.stop(drain=False)``) — the request was NOT
    served and will not be; resubmit after ``start()`` if the work is
    still wanted. Typed so waiters can tell an orderly shutdown from a
    dispatch failure."""


class PoisonedRequestError(RuntimeError):
    """A request exhausted its bounded retries (``max_requeues``
    requeue-at-front attempts all failed) — retrying again would loop
    forever, since a permanently-bad request coalesces with every later
    submit to its model. The last underlying dispatch error rides in
    ``__cause__``."""


def _resolve_future(fut: Future | None, *, result=None,
                    error: BaseException | None = None) -> None:
    """Resolve a request future, tolerating a caller-side cancel racing the
    resolution (futures here are never set_running, so ``cancel()`` can win
    between our done() check and set_result — an InvalidStateError leaking
    out of the resolution loop would strand every later future in the
    round)."""
    if fut is None or fut.done():
        return
    try:
        if error is not None:
            fut.set_exception(error)
        else:
            fut.set_result(result)
    except concurrent.futures.InvalidStateError:
        pass    # cancelled mid-resolution: the caller owns that outcome


def make_serve_step(cfg: ArchConfig):
    def serve_step(params, state, tokens, pos, enc_out=None):
        logits, new_state = decode_step(cfg, params, state, tokens, pos,
                                        enc_out=enc_out)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, new_state

    return serve_step


def make_prefill_step(cfg: ArchConfig, *, last_only: bool = True):
    def prefill_step(params, batch):
        logits, _ = forward_train(cfg, params, batch, last_only=last_only)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    return prefill_step


class Server:
    """Minimal batched greedy-decode server (the paper-kind is inference)."""

    def __init__(self, cfg: ArchConfig, mesh, *, kv_len: int = 512,
                 batch_size: int = 8, dtype=jnp.float32):
        self.cfg, self.mesh = cfg, mesh
        params = init_model(cfg, jax.random.PRNGKey(0), dtype=dtype)
        self.param_sh = named(mesh, param_specs(cfg, params, mesh))
        self.params = jax.device_put(params, self.param_sh)
        state = init_decode_state(cfg, batch_size, kv_len, dtype=dtype)
        self.state_sh = named(
            mesh, decode_state_specs(cfg, state, mesh, batch_size=batch_size))
        self.state = jax.device_put(state, self.state_sh)
        self.batch_size = batch_size
        self._step = jax.jit(
            make_serve_step(cfg),
            in_shardings=(self.param_sh, self.state_sh, None, None),
            out_shardings=(None, self.state_sh),
            donate_argnums=(1,),
        )

    def generate(self, prompt_tokens: np.ndarray, max_new: int = 16) -> np.ndarray:
        """Greedy continuation for a batch of single-token prompts."""
        toks = jnp.asarray(prompt_tokens[:, :1], jnp.int32)
        out = [toks]
        for t in range(max_new):
            toks, self.state = self._step(self.params, self.state, toks, jnp.int32(t))
            out.append(toks)
        return np.concatenate([np.asarray(t) for t in out], axis=1)


class PegasusServer:
    """Batched multi-request server over ONE cached ExecutionPlan.

    The plan is compiled once in ``__init__`` (feature one-hots, padded
    LUT/threshold tensors, int8 LUT + scales); every request batch after
    that dispatches the whole-plan JITTED forward — the batch is padded up
    to its compile bucket (powers of two by default), so arbitrary request
    sizes hit a warm XLA executable instead of retracing per shape.
    Requests may be single inputs or tuples (e.g. ``(seq, payload)`` for
    CNN-L); requests are fused into one plan call, chunked along the
    bucket ladder (``repro.engine.bucket_chunks``) so full chunks are exact
    bucket sizes, and the outputs split back out. ``stats()`` reports the
    compile-cache counters (traces vs bucket hits).

    Every request input MUST carry a leading batch dim (wrap a single flow
    as ``x[None]``) — axis 0 is always interpreted as the batch axis.

    Serving counters are incremented ONLY after the plan call succeeds — a
    raising request (bad shape, unknown backend) must not corrupt
    ``requests_served``/``batches_run``.
    """

    def __init__(self, model, *, backend: str = "onehot",
                 interpret: bool | None = None, max_batch: int | None = None,
                 fuse: bool = True):
        from repro.engine import build_plan

        t0 = time.perf_counter()
        self.plan = build_plan(model, backend=backend, interpret=interpret,
                               fuse=fuse)
        self.plan_build_ms = (time.perf_counter() - t0) * 1e3
        self.backend = backend
        # default cap = the top of the plan's bucket ladder (4096), so a
        # coalesced batch that has its own exact bucket is never split
        self.max_batch = (max(self.plan.buckets) if max_batch is None
                          else max_batch)
        self.requests_served = 0
        self.batches_run = 0
        self.flows_served = 0

    def stats(self) -> dict:
        """Unified serving-stats schema (shared across all three servers —
        see docs/SERVING.md): ``serving`` carries the request counters,
        ``engine`` the plan build + compile-cache stats (a bucket_hits to
        traces ratio near 1:1 means the bucket ladder is mis-sized);
        ``scheduler``/``slo`` are empty here (no queueing on this server)
        and ``devices`` reports the plan's device count."""
        ndev = 1 if self.plan.devices is None else len(self.plan.devices)
        return {
            "backend": self.backend,
            "serving": {
                "requests_served": self.requests_served,
                "batches_run": self.batches_run,
                "flows_served": self.flows_served,
                "batches_dispatched": self.batches_run,
            },
            "engine": {
                "plan_build_ms": self.plan_build_ms,
                "num_banks": self.plan.num_banks,
                "table_bytes": self.plan.table_bytes(),
                **self.plan.compile_stats(),
            },
            "scheduler": {},
            "slo": {},
            "devices": {"count": ndev, "per_device": []},
            # schema-uniform with the multi-model servers: one plan, no
            # queue, no breakers — nothing to heal
            "health": {"models": {}, "degraded_models": [],
                       "chaos": {"installed": False}},
        }

    def infer(self, *inputs, backend: str | None = None) -> jax.Array:
        """One already-batched call through the cached plan (one request)."""
        y = self.plan(*inputs, backend=backend)
        self.batches_run += 1            # success-only counting
        self.requests_served += 1
        self.flows_served += int(np.shape(inputs[0])[0])
        return y

    def serve(self, requests, *, backend: str | None = None) -> list:
        """Fuse a list of requests into bucket-aligned batches, split results.

        The typed surface: a list of :class:`InferRequest` returns a list
        of :class:`InferResult` (request order). This server dispatches
        immediately — there is no queue, so ``deadline_ms``/``priority``
        on the requests are accepted but have nothing to act on (use
        ``MultiModelServer`` for scheduled serving). The legacy shape — a
        list of bare arrays / input tuples returning raw ``np.ndarray``
        outputs — still works as a deprecated shim."""
        from repro.engine import bucket_chunks

        reqs, typed = _as_requests(requests, named=False)
        if not reqs:
            return []
        if not typed:
            _warn_legacy("PegasusServer.serve(list of arrays)",
                         "pass a list of InferRequest")
        cat, sizes, total = _coalesce([r.inputs for r in reqs])
        chunks, start = [], 0
        for size in bucket_chunks(total, self.plan.buckets, self.max_batch):
            sl = (cat if size == total
                  else [c[start : start + size] for c in cat])
            chunks.append(self.plan(*sl, backend=backend))
            start += size
        out = jnp.concatenate(chunks, axis=0) if len(chunks) > 1 else chunks[0]
        # commit counters only once EVERY chunk dispatched — a failure on a
        # later chunk must not leave batches_run ahead of requests_served
        self.batches_run += len(chunks)
        self.requests_served += len(sizes)
        self.flows_served += total
        split = _split(out, sizes)
        if not typed:
            return split
        return [InferResult(r.model, o, n)
                for r, o, n in zip(reqs, split, sizes)]


def _coalesce(requests, *, host: bool = False) -> tuple[list, list[int], int]:
    """Normalize a request list (arrays or input tuples, each with a leading
    batch dim) into per-input concatenations + per-request sizes. ``host``
    keeps host inputs on the host (a device pool places each chunk on its
    own device; a jnp concatenation would first route it through the
    default device)."""
    reqs = [tuple(r) if isinstance(r, (tuple, list)) else (r,) for r in requests]
    sizes = [int(np.shape(r[0])[0]) for r in reqs]
    xp = np if host else jnp
    if len(reqs) == 1:
        cat = [r if isinstance(r, jax.Array) else xp.asarray(r)
               for r in reqs[0]]
    else:
        cat = [xp.concatenate([xp.asarray(r[i]) for r in reqs], axis=0)
               for i in range(len(reqs[0]))]
    return cat, sizes, sum(sizes)


def _on_stream(pool, plan, chunk: tuple, backend, device) -> np.ndarray:
    """One chunk on a device-stream worker: the plan call, the wait for its
    result and the copy to the host, so the drain thread blocks on none of
    them. ``assert_worker`` pins "ALL plan calls run on pool workers" under
    the sanitizer (a no-op unless enabled)."""
    pool.assert_worker()
    y = plan(*chunk, backend=backend, device=device)
    _wait_if_traced(y)
    with TraceAnnotation("serve.to_host"):
        return np.asarray(y)


def _wait_if_traced(y: jax.Array) -> None:
    """While a profiler records, wait for ``y`` in a ``serve.wait`` span of
    its own, so the trace parts the wait on the device from the copy to the
    host that follows. The copy is enqueued first, as ``np.asarray`` alone
    would, so the device starts it the moment the result is ready. With no
    profiler the copy waits by itself: the separate wait is a second
    blocking call, and cost MLP-B about 3 % of its flows/s on a TPU v5e."""
    if TraceAnnotation.is_enabled():
        y.copy_to_host_async()
        with TraceAnnotation("serve.wait"):
            jax.block_until_ready(y)


def _split(out: jax.Array, sizes: list[int]) -> list[np.ndarray]:
    """Cut a coalesced output back into per-request numpy arrays."""
    if len(sizes) == 1:
        return [np.asarray(out)]
    return [np.asarray(o)
            for o in jnp.split(out, np.cumsum(sizes)[:-1], axis=0)]


class MultiModelServer:
    """Many heterogeneous models behind ONE server.

    Each model is compiled once and pinned under a name in a
    :class:`repro.engine.PlanRegistry` (per-model backend override allowed).
    Requests address models by name; pending same-model requests are
    coalesced into bucket-aligned micro-batches (``bucket_chunks``: full
    chunks are exact bucket sizes, the tail pads minimally) and models with
    pending work are scheduled by **weighted fair queueing**
    (:class:`~repro.launch.scheduler.WFQScheduler`: deficit round-robin,
    each model's flow share proportional to its priority weight — with
    every model at the default weight this degenerates to the PR-3
    one-chunk-per-model round-robin), so a burst on one model cannot
    starve the others and a high-priority model is served first.

    Two call styles:
      * ``infer(name, *inputs)`` — immediate single-request dispatch.
      * ``submit(name, *inputs)`` + ``drain()`` — enqueue across models,
        then serve everything; ``drain`` returns ``{name: [out_per_request]}``
        in per-model submit order. ``serve(requests)`` wraps submit+drain
        for a mixed ``[(name, inputs), ...]`` list, preserving order.

    Ingestion is thread-safe: the scheduler owns every queue behind one
    lock, so concurrent ``submit``/``add_model`` during a ``drain`` can
    neither corrupt the queue map (the old dict-iteration RuntimeError) nor
    lose requests (the old drain ``clear()``-ed whole queues at commit,
    wiping anything submitted mid-drain — requests are now popped
    individually). Plan dispatch itself stays on the draining thread.

    All counters (``requests_served``/``batches_run``/``flows_served``) are
    per model and committed only when a pulled slice fully serves; a
    failing slice is requeued at the front (retryable, never
    double-counted), its exception lands in ``last_drain_errors``, and
    every other model drains and returns normally. ``schedule_log`` records
    the model name of every dispatched micro-batch — the fairness tests
    assert on it.
    """

    def __init__(self, models: dict | None = None, *, backend: str = "onehot",
                 interpret: bool | None = None, max_batch: int | None = None,
                 registry=None, fuse: bool = True,
                 queue_depth: int | None = None, policy: str = "block",
                 quantum: int | None = None, devices=None,
                 breaker_failures: int = 3, breaker_reset_s: float = 1.0,
                 max_requeues: int = 5, retry_backoff_s: float = 0.02):
        from repro.engine import DEFAULT_BUCKETS, PlanRegistry
        from repro.engine.plan import resolve_devices

        self.registry = PlanRegistry() if registry is None else registry
        # devices: fan dispatch out across N device streams — each pulled
        # chunk is placed on the least-loaded device's executor queue and
        # runs there via per-call placement (plan state replicated per
        # device, see ExecutionPlan.__call__(device=)). None (the default)
        # keeps the single-stream inline dispatch; an EXPLICIT devices=1
        # still gets a one-stream pool so scaling comparisons across K run
        # one code path (the sharding bench gates K=4 against K=1).
        self.devices = resolve_devices(devices)
        self._pool = (DeviceStreamPool(self.devices)
                      if self.devices else None)
        self.backend = backend
        self.interpret = interpret
        self.fuse = fuse    # cross-bank fusion default for add_model plans
        self.max_batch = (max(DEFAULT_BUCKETS) if max_batch is None
                          else max_batch)
        self.queue_depth = queue_depth   # default bound for new model queues
        self.policy = policy             # default backpressure policy
        # DRR credit per round per unit weight, in flows. None → max_batch
        # (a weight-1 model earns ~one full micro-batch per round). Set it
        # SMALLER to ration deep backlogs across more rounds — finer-grained
        # priority differentiation at slightly more scheduling overhead.
        self.quantum = quantum
        self._sched = WFQScheduler()
        # counter commits are read-modify-writes shared between the drain
        # thread and infer() callers — same race the plan-level counters
        # guard with _PlanCounters.lock
        self._ctr_lock = make_lock("serve._ctr_lock")
        self._counters: dict[str, dict] = {}        # guarded-by: _ctr_lock
        # bounded: the log is a debugging/fairness-test surface, not an
        # audit trail — a long-lived server must not grow it without limit.
        # Deliberately NOT guarded-by-annotated: deque.append is atomic
        # under the GIL and readers tolerate a stale tail.
        self.schedule_log: deque = deque(maxlen=4096)
        self.batches_dispatched = 0                 # guarded-by: _ctr_lock
        # bound by the async drain loop (never for the caller-driven sync
        # server): once bound, all dispatch must happen on that thread
        self._dispatch_affinity = ThreadAffinity("dispatch")
        self.last_drain_errors: dict[str, Exception] = {}
        self.last_shed: dict[str, int] = {}   # sheds seen by the last drain
        # -- self-healing (docs/RELIABILITY.md) -----------------------------
        # Per-model breakers guard the PREFERRED backend path: after
        # breaker_failures consecutive slice failures the model serves
        # DEGRADED on the gather fallback until a cooldown probe back on
        # the preferred path succeeds. max_requeues bounds the deadline-
        # aware retry (requeue-at-front) so a poison-pill request fails
        # typed PoisonedRequestError instead of looping forever.
        self.breaker_failures = int(breaker_failures)    # immutable config
        self.breaker_reset_s = float(breaker_reset_s)    # immutable config
        self.max_requeues = int(max_requeues)            # immutable config
        self.retry_backoff_s = float(retry_backoff_s)    # immutable config
        self._breakers: dict[str, CircuitBreaker] = {}  # guarded-by: _ctr_lock
        self._health_ctrs: dict[str, dict] = {}         # guarded-by: _ctr_lock
        # retry pacing, touched ONLY by the dispatch thread (the sync
        # drain caller or the async loop — the same single-dispatcher
        # exclusivity _dispatch_affinity pins), so deliberately unguarded
        # like schedule_log
        self._retry_streak: dict[str, int] = {}
        self._retry_not_before: dict[str, float] = {}
        # drain-round number carried by the serve.round and devices.run
        # trace spans; dispatch thread only, like the retry pacing above
        self._round = 0
        # fault-injection hook — None until install_chaos(); the hot path
        # pays one is-None check per dispatched slice (repro.launch.chaos)
        self._chaos = None
        for name in self.registry.names():   # adopt a pre-populated registry
            self._track(name)
        for name, model in dict(models or {}).items():
            self.add_model(name, model)

    def _track(self, name: str, **sched_kw) -> None:
        """Queue + counters for a registry name this server serves. Names
        registered on a shared registry after construction are adopted
        lazily on first submit/infer. Server-wide depth/policy defaults
        apply only at queue CREATION — an existing queue keeps its config
        unless the caller passed explicit overrides."""
        if name not in self._sched:
            sched_kw.setdefault("depth", self.queue_depth)
            sched_kw.setdefault("policy", self.policy)
        self._sched.add_queue(name, **sched_kw)
        with self._ctr_lock:
            self._counters.setdefault(name, {"requests_served": 0,
                                             "batches_run": 0,
                                             "flows_served": 0})
            if name not in self._breakers:
                self._breakers[name] = CircuitBreaker(
                    name, failure_threshold=self.breaker_failures,
                    reset_timeout_s=self.breaker_reset_s)
            self._health_ctrs.setdefault(name, {"fallback_batches": 0,
                                                "probe_batches": 0,
                                                "retries": 0,
                                                "poisoned": 0,
                                                "deadline_dropped": 0})

    def _breaker(self, name: str) -> CircuitBreaker | None:
        with self._ctr_lock:
            return self._breakers.get(name)

    def _tracked(self, name: str) -> None:
        with self._ctr_lock:
            known = name in self._counters
        if not known:
            if name not in self.registry:
                raise KeyError(
                    f"unknown model {name!r}; registered: {self.models()}")
            self._track(name)

    def _quantum(self) -> int:
        """DRR credit per round per unit weight, in flows — by default the
        effective micro-batch ceiling, so a weight-1 model earns about one
        full micro-batch per round and a weight-4 model earns four."""
        return max(1, int(self.max_batch if self.quantum is None
                          else self.quantum))

    # -- model management ---------------------------------------------------

    def add_model(self, name: str, model, *, backend: str | None = None,
                  priority: str | None = None, weight: float | None = None,
                  queue_depth: int | None = None, policy: str | None = None,
                  **build_kw):
        """Compile + register one model; returns its ExecutionPlan.

        Args:
            name: the serving handle requests address; re-registering an
                existing name rebuilds its plan and re-applies any
                explicit scheduling fields below.
            model: the Pegasus bank structure to compile (whatever
                ``repro.engine.build_plan`` accepts).
            backend: engine backend for this plan (``gather | onehot |
                kernel | kernel_q8``); ``None`` uses the server default.
            priority: a class in :data:`PRIORITY_WEIGHTS` (``"high"`` = 4x
                the flow share of ``"normal"``; ``"low"`` = 0.25x).
            weight: explicit WFQ weight (flows-per-round multiplier);
                overrides ``priority``. Must be > 0.
            queue_depth: max queued requests for this model (``None`` =
                server default; unbounded if that is also ``None``).
            policy: backpressure when the bounded queue is full —
                ``"reject"`` raises :class:`QueueFullError` at submit,
                ``"block"`` parks the submitter until space frees.
            **build_kw: forwarded to ``build_plan`` (``fuse``,
                ``bucket_sizes``, ``strategy``, ... — see its docstring).

        Raises:
            ValueError: unknown ``priority``/``policy``, or
                non-positive ``weight``/``queue_depth``.
        """
        build_kw.setdefault("fuse", self.fuse)
        plan = self.registry.register(
            name, model, backend=backend or self.backend,
            interpret=self.interpret, **build_kw)
        sched_kw: dict = {"priority": priority, "weight": weight}
        if queue_depth is not None:
            sched_kw["depth"] = queue_depth
        if policy is not None:
            sched_kw["policy"] = policy
        self._track(name, **sched_kw)   # explicit fields apply on re-register
        return plan

    def set_priority(self, name: str, *, priority: str | None = None,
                     weight: float | None = None) -> float:
        """Re-class a served model's WFQ weight (effective next round)."""
        self._tracked(name)
        return self._sched.set_weight(name, weight=weight, priority=priority)

    def remove_model(self, name: str) -> bool:
        """Evict a model; its pending queue is dropped with it (queued
        futures, if any, fail with KeyError)."""
        dropped = self._sched.remove_queue(name)
        err = KeyError(f"model {name!r} removed with requests pending")
        for r in dropped:
            _resolve_future(r.future, error=err)
        with self._ctr_lock:
            self._counters.pop(name, None)
            self._breakers.pop(name, None)
            self._health_ctrs.pop(name, None)
        return self.registry.evict(name)

    def models(self) -> list[str]:
        return self.registry.names()

    def install_chaos(self, injector) -> None:
        """Wire a :class:`repro.launch.chaos.FaultInjector` into every
        dispatch edge this server owns — its own plan-call edge, the
        registry's plan-build edge, and the device pool's stream-dispatch
        edge — in one call. Explicit hooks, never monkey-patching; with no
        injector installed every edge costs one ``is None`` check."""
        self._chaos = injector
        self.registry.chaos = injector
        if self._pool is not None:
            self._pool.chaos = injector

    def uninstall_chaos(self) -> None:
        """Detach the injector from every hook :meth:`install_chaos` set."""
        self._chaos = None
        self.registry.chaos = None
        if self._pool is not None:
            self._pool.chaos = None

    # -- request paths ------------------------------------------------------

    def infer(self, request, *legacy_inputs, backend: str | None = None):
        """Immediate single-request dispatch through the named plan — no
        queueing, no coalescing, no deadline (the request runs NOW on the
        calling thread; a typed request's ``deadline_ms``/``priority``
        have no queue to act on). ``backend`` optionally overrides the
        plan's compiled backend for this call.

        The typed surface takes one :class:`InferRequest` and returns an
        :class:`InferResult`; the legacy ``infer(name, *inputs)`` shape
        (raw output, deprecated) still works. Raises ``KeyError`` for an
        unknown name; plan errors (bad shape, unknown backend) propagate
        without touching the counters."""
        if isinstance(request, InferRequest):
            if legacy_inputs:
                raise TypeError(
                    "infer(InferRequest) takes no extra positional inputs "
                    "— they ride in request.inputs")
            name, inputs = request.model, request.inputs
        else:
            _warn_legacy("MultiModelServer.infer(name, *inputs)",
                         "pass an InferRequest")
            name, inputs = request, legacy_inputs
        self._tracked(name)
        y = self.registry.get(name)(*inputs, backend=backend)
        flows = int(np.shape(inputs[0])[0])
        with self._ctr_lock:
            c = self._counters[name]
            c["requests_served"] += 1    # success-only counting
            c["batches_run"] += 1
            c["flows_served"] += flows
        if isinstance(request, InferRequest):
            return InferResult(name, y, flows)
        return y

    def _enqueue(self, name: str, inputs: tuple, future: Future | None,
                 timeout: float | None,
                 deadline_ms: float | None = None,
                 priority: str = "normal") -> int:
        with TraceAnnotation("serve.submit"):
            self._tracked(name)
            # stage host inputs on the device now, off the dispatch thread
            # — except in front of a device pool, whose streams each take
            # their chunks straight from the host
            xp = np if self._pool is not None else jnp
            inputs = tuple(x if isinstance(x, jax.Array) else xp.asarray(x)
                           for x in inputs)
            return self._sched.submit(
                name, inputs, int(np.shape(inputs[0])[0]), future=future,
                timeout=timeout, deadline_ms=deadline_ms, priority=priority)

    def submit(self, request, *legacy_inputs, timeout: float | None = None,
               deadline_ms: float | None = None) -> int:
        """Enqueue one :class:`InferRequest` for the next :meth:`drain`.

        Args:
            request: the typed request — model name, input arrays (each
                with a LEADING BATCH DIM; wrap a single flow as
                ``x[None]``; a model of several inputs takes a tuple; the
                dtype is kept, so CNN-L's uint8 records stay 1 byte per
                value), optional ``deadline_ms`` budget, and per-request
                ``priority`` (queue-jump within this model's queue — see
                :data:`~repro.launch.scheduler.PRIORITY_RANK`). The legacy
                ``submit(name, *inputs, deadline_ms=...)`` shape still
                works as a deprecated shim.
            timeout: seconds to wait for queue space when the model queue
                is bounded with ``policy="block"``; ``None`` waits forever.
                Expiry raises :class:`QueueFullError`.
            deadline_ms: legacy-shape only (typed requests carry their own
                ``deadline_ms``).

        Returns:
            The request's queue position at insert time (0-based).

        Raises:
            KeyError: unknown model name.
            QueueFullError: bounded queue full (``policy="reject"``, or
                ``block`` timed out) — also raised at admission when the
                queue's ``admit_ms`` horizon is exceeded.
            DeadlineExceededError: admission control predicts the deadline
                cannot be met given the observed service rate (the
                scheduler may also shed the queued request later at pull
                time, failing its future with the same error).
            ValueError: non-positive ``deadline_ms``.
        """
        if isinstance(request, InferRequest):
            if legacy_inputs or deadline_ms is not None:
                raise TypeError(
                    "submit(InferRequest) takes no extra inputs or "
                    "deadline_ms — they ride in the request")
            return self._enqueue(request.model, request.inputs, None,
                                 timeout, deadline_ms=request.deadline_ms,
                                 priority=request.priority)
        _warn_legacy("MultiModelServer.submit(name, *inputs)",
                     "pass an InferRequest")
        return self._enqueue(request, legacy_inputs, None, timeout,
                             deadline_ms=deadline_ms)

    def pending(self) -> dict[str, int]:
        return self._sched.pending()

    def discard_pending(self, name: str) -> int:
        """Drop a model's queued requests (returns how many). The escape
        hatch for a poisoned queue: a permanently-bad request is coalesced
        with every later submit to its model, so retries would fail
        forever until the queue is cleared. Dropped futures are cancelled
        (or failed, if already running)."""
        dropped = self._sched.discard(name)
        err = RuntimeError(f"request discarded from {name!r}'s queue")
        for r in dropped:
            if r.future is not None and not r.future.done():
                if not r.future.cancel():
                    r.future.set_exception(err)
        return len(dropped)

    # -- dispatch -----------------------------------------------------------

    def _begin_group(self, name: str, reqs: list, backend: str | None) -> dict:
        """Phase 1 of serving one pulled slice: coalesce → bucket_chunks
        micro-batches → plan calls. JAX dispatch is asynchronous, so this
        returns as soon as every chunk is ENQUEUED on the device — the
        caller begins every group in the round before finishing any, which
        keeps the device pipeline full across models (blocking on model A's
        results before dispatching model B serialized the round and cost
        ~2x aggregate throughput). With a multi-device pool, each chunk is
        instead handed to the LEAST-LOADED device stream (fewest pending
        flows, ties → lowest device index) and ``outs`` holds the pool
        futures. Returns a group record; a dispatch failure rides in its
        ``"error"`` key."""
        from repro.engine import bucket_chunks

        with TraceAnnotation("serve.begin",
                             flows=sum(r.size for r in reqs)):
            # sanitizer checkpoint: once the async loop binds the dispatch
            # affinity, ANY other thread reaching this dispatch edge is the
            # "two concurrent dispatchers" bug (unbound → free, sync path)
            self._dispatch_affinity.assert_here()
            t0 = time.perf_counter()
            # queue-wait ends HERE, not at pull time: a round's groups
            # dispatch sequentially, so later (lower-priority) groups keep
            # waiting while earlier ones run — the stamp must capture that
            # ordering effect
            for r in reqs:
                r.t_dispatch = t0
            # "managed" = no explicit caller backend override: only managed
            # groups ride the fallback ladder and feed the model's breaker
            # (an explicit per-drain backend is the caller experimenting,
            # not the serving path the breaker guards)
            g: dict = {"name": name, "reqs": reqs, "t0": t0,
                       "degraded": False, "probe": False,
                       "managed": backend is None}
            try:
                br = self._breaker(name) if g["managed"] else None
                if br is not None and br.state != CLOSED:
                    # fallback ladder: the preferred-path breaker is
                    # tripped. A granted cooldown probe retries the
                    # preferred backend (success auto-reinstates);
                    # otherwise this slice serves DEGRADED on the gather
                    # fallback plan — same model, same tables,
                    # least-machinery backend.
                    if br.allow():
                        g["probe"] = True
                    else:
                        g["degraded"] = True
                if self._chaos is not None:
                    self._chaos.fire(
                        "plan_call", model=name,
                        backend=(FALLBACK_BACKEND if g["degraded"] else
                                 backend or self.registry.backend_of(name)))
                if g["degraded"]:
                    plan = self.registry.get_with_backend(
                        name, FALLBACK_BACKEND)
                else:
                    plan = self.registry.get(name)
                if g["degraded"] or g["probe"]:
                    with self._ctr_lock:
                        h = self._health_ctrs.get(name)
                        if h is not None:
                            key = ("fallback_batches" if g["degraded"]
                                   else "probe_batches")
                            h[key] += 1
                cat, sizes, total = _coalesce([r.inputs for r in reqs],
                                              host=self._pool is not None)
                chunks = bucket_chunks(total, plan.buckets, self.max_batch)
                outs, start = [], 0
                for size in chunks:
                    sl = (cat if start == 0 and size == total
                          else [c[start : start + size] for c in cat])
                    if self._pool is None:
                        outs.append(plan(*sl, backend=backend))
                    else:
                        # the chunk runs on whichever stream has the least
                        # pending work; np conversion happens ON that
                        # worker so the block is off this thread too
                        outs.append(self._pool.submit(
                            lambda d, plan=plan, sl=tuple(sl): _on_stream(
                                self._pool, plan, sl, backend, d),
                            size, round_id=self._round))
                    self.schedule_log.append(name)
                    with self._ctr_lock:
                        self.batches_dispatched += 1
                    start += size
            except Exception as e:
                g["error"] = e
                return g
            g.update(outs=outs, sizes=sizes, total=total,
                     batches=len(chunks), t_begun=time.perf_counter())
            return g

    def _finish_group(self, g: dict):
        """Phase 2: block on the group's device results, split per request,
        commit counters, record latency, resolve futures. On failure the
        model's breaker records it (preferred path only) and the slice goes
        through deadline-aware bounded retry — requeue-at-front, capped by
        ``max_requeues``, never past a request's own deadline (see
        :meth:`_retry_or_fail`). Returns the per-request np outputs, or
        None on failure."""
        name, reqs = g["name"], g["reqs"]
        err = g.get("error")
        if err is None:
            t_finish = time.perf_counter()
            try:
                if self._pool is not None:
                    # pool mode: outs are futures of per-chunk NP arrays on
                    # DIFFERENT devices — concatenate on the host (jnp
                    # would refuse to mix committed devices)
                    with TraceAnnotation("serve.wait"):
                        arrs = [f.result() for f in g["outs"]]
                    out = (np.concatenate(arrs, axis=0)
                           if len(arrs) > 1 else arrs[0])
                    split = ([out] if len(g["sizes"]) == 1 else
                             np.split(out, np.cumsum(g["sizes"])[:-1],
                                      axis=0))
                else:
                    out = (jnp.concatenate(g["outs"], axis=0)
                           if len(g["outs"]) > 1 else g["outs"][0])
                    _wait_if_traced(out)
                    with TraceAnnotation("serve.to_host"):
                        split = _split(out, g["sizes"])
            except Exception as e:
                err = e
        # the breaker sees only the PREFERRED path: a degraded (fallback)
        # slice neither extends nor resets the preferred path's streak
        br = (self._breaker(name)
              if g.get("managed", True) and not g.get("degraded") else None)
        if err is not None:
            self.last_drain_errors[name] = err
            if br is not None:
                br.record_failure()
            self._retry_or_fail(name, reqs, err, probe=g.get("probe", False))
            return None
        if br is not None:
            br.record_success()      # probe success auto-reinstates
        self._retry_streak.pop(name, None)
        self._retry_not_before.pop(name, None)
        # service = this group's own dispatch phase + its own blocking
        # finish — NOT wall time since begin, which would fold every
        # earlier group's host conversion into later (lower-priority)
        # groups' service percentiles. Still approximate under concurrent
        # device work, but free of that systematic ordering bias.
        service_ms = ((g["t_begun"] - g["t0"])
                      + (time.perf_counter() - t_finish)) * 1e3
        self._sched.record_service(name, reqs, service_ms)
        with self._ctr_lock:
            # .get: the model may have been remove_model'd while this slice
            # was in flight — its results still resolve, only the counters
            # have nowhere to go (a KeyError here would strand the futures)
            c = self._counters.get(name)
            if c is not None:
                c["requests_served"] += len(reqs)
                c["batches_run"] += g["batches"]
                c["flows_served"] += g["total"]
        for r, o in zip(reqs, split):
            if r.future is not None:
                # observed submit→dispatch wait, for InferResult
                # telemetry: the typed paths read it off the settled future
                r.future.queue_wait_ms = (r.t_dispatch - r.t_submit) * 1e3
            _resolve_future(r.future, result=o)
        return split

    def _retry_or_fail(self, name: str, reqs: list, err: Exception, *,
                       probe: bool = False) -> None:
        """Failure triage for one slice — deadline-aware bounded retry.

        Per request: a deadline already burned through fails NOW with the
        dispatch error (never retry past a request's own ``deadline_ms``);
        a request at ``max_requeues`` fails typed
        :class:`PoisonedRequestError` (the dispatch error in
        ``__cause__``); everything else is requeued at the FRONT (retry
        order preserved) with its requeue count bumped. A failed breaker
        PROBE requeues without charging the count — the probe was the
        server's experiment, not the request's fault. Consecutive failed
        slices back off exponentially (``retry_backoff_s`` doubling, capped
        at 1 s): the async loop excludes the model until the pause expires,
        the sync drain's per-call exclusion makes pacing moot."""
        now = time.perf_counter()
        survivors: list = []
        n_deadline = n_poison = 0
        for r in reqs:
            if (r.deadline_ms is not None
                    and (now - r.t_submit) * 1e3 >= r.deadline_ms):
                _resolve_future(r.future, error=err)
                n_deadline += 1
            elif not probe and r.requeues >= self.max_requeues:
                perr = PoisonedRequestError(
                    f"request for {name!r} failed {r.requeues + 1} times "
                    f"(max_requeues={self.max_requeues}); giving up — "
                    "discard or fix the request")
                perr.__cause__ = err
                _resolve_future(r.future, error=perr)
                n_poison += 1
            else:
                if not probe:
                    r.requeues += 1
                survivors.append(r)
        if survivors:
            self._sched.requeue_front(name, survivors)
            streak = self._retry_streak.get(name, 0)
            self._retry_not_before[name] = now + min(
                self.retry_backoff_s * (2 ** streak), 1.0)
            self._retry_streak[name] = streak + 1
        with self._ctr_lock:
            h = self._health_ctrs.get(name)
            if h is not None:
                h["retries"] += len(survivors)
                h["poisoned"] += n_poison
                h["deadline_dropped"] += n_deadline

    def drain(self, *, backend: str | None = None) -> dict:
        """Serve every queued request: the WFQ scheduler releases per-model
        slices (deficit round-robin: ``quantum x weight`` flows of credit
        per round, descending-weight dispatch order), each slice coalesces
        and cuts into bucket-aligned micro-batches. Returns
        ``{name: [np.ndarray per request, in submit order]}``.

        Failures are isolated per model: a slice whose dispatch raises is
        requeued at the front (retryable) with ALL its counters untouched
        (they only commit when a slice fully serves — a retry never
        double-counts partially-run chunks), the model is excluded for the
        rest of this drain, and every other model drains normally. The
        per-model exceptions land in ``last_drain_errors``; drain raises
        only if NO model succeeded. The retry is BOUNDED: a request that
        fails ``max_requeues`` requeues fails typed
        :class:`PoisonedRequestError` instead of looping forever (or clear
        the queue sooner with ``discard_pending``), and a request whose own
        ``deadline_ms`` has burned through is never retried at all.

        Deadline-bearing requests whose slack ran out while queued are
        SHED by the scheduler (dropped, future failed with
        :class:`DeadlineExceededError`) and do not appear in the returned
        lists; ``last_shed`` records ``{name: count}`` for this drain."""
        self.last_drain_errors = {}
        results: dict = {}
        failed: set = set()
        quantum = self._quantum()
        while True:
            self._round += 1
            with TraceAnnotation("serve.round", round=self._round):
                groups = self._sched.pull_round(quantum, exclude=failed)
                if not groups:
                    break
                # two phases: dispatch EVERY group, then block on each — the
                # device works across models while the host splits/converts
                begun = [self._begin_group(name, reqs, backend)
                         for name, reqs in groups]
                for g in begun:
                    outs = self._finish_group(g)
                    if outs is None:
                        failed.add(g["name"])  # skip for the rest of drain
                    else:
                        results.setdefault(g["name"], []).extend(outs)
        self.last_shed = {name: len(reqs)
                          for name, reqs in self._sched.take_shed().items()}
        if self.last_drain_errors and not results:
            raise next(iter(self.last_drain_errors.values()))
        return results

    def serve(self, requests, *, backend: str | None = None) -> list:
        """Mixed-model convenience: submit everything, drain, return
        results aligned to the request order.

        Args:
            requests: a list of :class:`InferRequest` (the typed surface —
                per-request ``deadline_ms`` and ``priority`` honored,
                returns :class:`InferResult` per request). The legacy
                shape — ``(name, inputs)`` / ``(name, inputs,
                deadline_ms)`` tuples returning raw outputs — still works
                as a deprecated shim.
            backend: per-drain engine backend override (sync drain only).

        Returns:
            One result per request, in request order — only when EVERY
            request served.

        Raises:
            PartialDrainError: any requested model failed to drain and/or
                any deadline-bearing request was shed. Served outputs ride
                in ``.partial_results`` (``{name: [outputs]}`` — that work
                is computed and counted), drain failures in ``.failed``,
                and shed requests in ``.shed``
                (``{name: [DeadlineExceededError]}``); shed work was never
                computed and only the failed/shed requests need
                resubmitting.
        """
        reqs, typed = _as_requests(requests, named=True)
        if not typed:
            _warn_legacy("MultiModelServer.serve(list of (name, inputs) "
                         "tuples)", "pass a list of InferRequest")
        order: list[tuple[InferRequest, Future]] = []
        for req in reqs:
            # a private future per request keeps served/shed alignment
            # robust: drain()'s per-model lists exclude shed requests, so
            # the old positional indexing into them would mis-align
            fut: Future = Future()
            try:
                self._enqueue(req.model, req.inputs, fut, None,
                              deadline_ms=req.deadline_ms,
                              priority=req.priority)
            except DeadlineExceededError as e:
                _resolve_future(fut, error=e)   # admission refusal == shed
            order.append((req, fut))
        by_model = self.drain(backend=backend)
        # a name in last_drain_errors did NOT fully serve — including a
        # model whose earlier slice landed in by_model before a later slice
        # failed (drain excludes it from then on), so membership in
        # by_model alone must not count as success
        failed = {name: self.last_drain_errors[name]
                  for name in dict.fromkeys(r.model for r, _ in order)
                  if name in self.last_drain_errors}
        shed: dict[str, list] = {}
        for req, fut in order:
            if fut.done():
                exc = fut.exception()
                if isinstance(exc, DeadlineExceededError):
                    shed.setdefault(req.model, []).append(exc)
        if failed or shed:
            cause = (next(iter(failed.values())) if failed
                     else next(iter(shed.values()))[0])
            raise PartialDrainError(failed, by_model, shed=shed) from cause
        if not typed:
            return [fut.result() for _, fut in order]
        return [InferResult(req.model, fut.result(), req.flows,
                            queue_wait_ms=getattr(fut, "queue_wait_ms", None))
                for req, fut in order]

    def close(self) -> None:
        """Release the per-device executor threads (multi-device servers
        only; a no-op otherwise). Queued device work finishes first."""
        if self._pool is not None:
            self._pool.close()

    def stats(self) -> dict:
        """The unified serving-stats schema (shared with ``PegasusServer``
        and ``AsyncMultiModelServer`` — field-by-field reference in
        docs/SERVING.md): ``serving`` carries the per-model + aggregate
        request counters, ``engine`` the registry cache plus per-model
        plan build/compile-cache stats, ``scheduler`` the queue config and
        latency percentiles, ``slo`` the per-model SLO counters
        (admission/shed/goodput/starvation), ``devices`` the per-device
        stream utilization/depth (multi-device servers), and ``health``
        the self-healing state — per-model breaker + fallback/retry
        counters, ``degraded_models``, and the installed chaos injector
        (docs/RELIABILITY.md)."""
        reg = self.registry.stats()
        zeros = {"requests_served": 0, "batches_run": 0, "flows_served": 0}
        # registry names BEFORE taking the counter lock: models() acquires
        # registry._lock (rank 0), outermost in the declared hierarchy —
        # nesting it under _ctr_lock (rank 2) is the inversion the runtime
        # sanitizer flagged on first enablement
        names = self.models()
        with self._ctr_lock:
            # zeroed defaults keep the schema uniform for names on a
            # shared registry that this server hasn't served yet; the
            # dispatch total snapshots in the SAME critical section so one
            # stats() call is internally consistent under a live drain
            per_model = {name: {**zeros, **self._counters.get(name, {})}
                         for name in names}
            batches_dispatched = self.batches_dispatched
            breakers = dict(self._breakers)
            hctrs = {n: dict(c) for n, c in self._health_ctrs.items()}
        # breaker snapshots AFTER releasing _ctr_lock: each stats() call
        # takes health._lock (rank 6 — legal under rank 2, but there is no
        # reason to hold the counter lock across N of them)
        health_models: dict = {}
        degraded_models: list = []
        for n in names:
            br = breakers.get(n)
            if br is None:
                continue
            bst = br.stats()
            is_degraded = bst["state"] != CLOSED
            if is_degraded:
                degraded_models.append(n)
            health_models[n] = {
                **bst, **hctrs.get(n, {}),
                "degraded": is_degraded,
                "preferred_backend": reg.get(n, {}).get("backend"),
                "fallback_backend": FALLBACK_BACKEND,
            }
        return {
            "backend": self.backend,
            "serving": {
                "requests_served": sum(m["requests_served"]
                                       for m in per_model.values()),
                "batches_run": sum(m["batches_run"]
                                   for m in per_model.values()),
                "flows_served": sum(m["flows_served"]
                                    for m in per_model.values()),
                "batches_dispatched": batches_dispatched,
                "models": per_model,
            },
            "engine": {
                "cache": self.registry.cache_info(),
                "models": reg,
            },
            "scheduler": {
                "models": self._sched.describe(),
                "latency": self._sched.latency_stats(),
            },
            "slo": {"models": self._sched.counters()},
            "devices": (self._pool.stats() if self._pool is not None
                        else {"count": 1, "per_device": []}),
            "health": {
                "models": health_models,
                "degraded_models": sorted(degraded_models),
                "chaos": (self._chaos.stats() if self._chaos is not None
                          else {"installed": False}),
            },
        }

    def slo_counters(self) -> dict:
        """The scheduler's per-model SLO counters alone (cheaper than full
        :meth:`stats`; see :meth:`WFQScheduler.counters` for the fields).
        The overload benchmark diffs these across phases."""
        return self._sched.counters()

    def reset_slo_counters(self) -> None:
        """Zero the SLO counters (benchmarks reset between load phases)."""
        self._sched.reset_counters()

    def reset_latency_stats(self) -> None:
        """Drop the latency reservoirs (benchmarks reset after warmup)."""
        self._sched.reset_latency()


class AsyncMultiModelServer(MultiModelServer):
    """The always-on :class:`MultiModelServer`: a background drain thread
    plus future-returning ``submit()``.

    ``submit(name, *inputs)`` is safe from any thread and returns a
    :class:`concurrent.futures.Future` resolving to the request's np output
    (or raising the dispatch error — async requests are NOT requeued on
    failure; the future carries the exception and the caller decides).
    Queues are bounded (``queue_depth``, default 1024 requests/model) with
    ``policy`` backpressure: ``"block"`` parks the submitter until the
    drain loop frees space (bounding producer speed to consumer speed),
    ``"reject"`` raises :class:`QueueFullError` immediately (shed load at
    ingestion, dataplane-style).

    The drain loop pulls one WFQ round at a time (so ``stop()`` stays
    responsive and priorities re-evaluate between rounds) and funnels every
    compiled-plan call through its single thread; ingestion touches the
    scheduler lock plus one ``device_put`` per input (inputs are staged to
    the device at submit time, on the producer's thread). Use as a context manager, or ``start()``/``stop()``:

        with AsyncMultiModelServer({"ids": banks}, queue_depth=256) as srv:
            futs = [srv.submit("ids", x) for x in bursts]
            outs = [f.result() for f in futs]

    ``stop(drain=True)`` (the default, and what ``__exit__`` calls) first
    waits for the queues to empty, then joins the loop — pending futures
    all resolve before stop returns.
    """

    def __init__(self, models: dict | None = None, *,
                 queue_depth: int | None = 1024, policy: str = "block",
                 idle_wait: float = 0.05, **kw):
        super().__init__(models, queue_depth=queue_depth, policy=policy, **kw)
        self._idle_wait = idle_wait
        self._stop_flag = threading.Event()
        self._thread: threading.Thread | None = None
        self.loop_errors: deque = deque(maxlen=64)   # unexpected loop crashes

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "AsyncMultiModelServer":
        """Spawn the background drain loop and return ``self`` (idempotent
        — a live loop is left alone; after ``stop()`` a fresh thread is
        spawned). Until start, submitted futures sit queued and never
        resolve; ``serve()``/``infer_async()`` refuse to run with the loop
        down rather than hang."""
        if self._thread is None or not self._thread.is_alive():
            self._stop_flag.clear()
            self._thread = threading.Thread(
                target=self._serve_loop, name="pegasus-drain", daemon=True)
            self._thread.start()
        return self

    def stop(self, *, drain: bool = True, timeout: float | None = None) -> None:
        """Stop the loop (what ``__exit__`` calls, with the defaults).

        Args:
            drain: wait for every queue to empty first, so in-flight
                futures all resolve before return; ``False`` halts after
                the current round and FAILS every still-pending future
                with :class:`ServerStoppedError` — a waiter blocked on
                ``future.result()`` unblocks instead of hanging forever
                (the old contract left them queued and unresolved).
            timeout: overall budget in SECONDS for drain-wait + join;
                ``None`` waits indefinitely. On expiry the loop may still
                be alive (``running`` stays true) and a later ``stop()``
                can finish the job — the thread is never abandoned while
                alive, which would let ``start()`` spawn a second
                concurrent dispatcher.
        """
        if self._thread is None:
            if not drain:
                # never started (or already stopped): the drain=False
                # contract still holds — no future may stay pending
                self._fail_pending_stopped()
            return
        deadline = None if timeout is None else time.monotonic() + timeout
        if drain:
            while self.pending() and self._thread.is_alive():
                if deadline is not None and time.monotonic() > deadline:
                    break
                time.sleep(0.002)
        self._stop_flag.set()
        self._sched.kick()
        self._thread.join(None if deadline is None
                          else max(0.0, deadline - time.monotonic()))
        # forget the thread only once it actually exited: after a timed-out
        # join the loop is still live, and untracking it would let start()
        # clear the stop flag and spawn a SECOND concurrent dispatcher
        if not self._thread.is_alive():
            self._thread = None
            if drain and self.pending():
                # a submit raced the stop flag (landed after the pending()
                # check, unseen by the exiting loop): honor the drain
                # contract by serving the stragglers inline, and fail any
                # future a failing slice would otherwise strand
                try:
                    self.drain()
                except Exception:
                    pass                        # recorded per model below
                for name in list(self.pending()):
                    err = self.last_drain_errors.get(name) or RuntimeError(
                        f"server stopped with {name!r} requests pending")
                    for r in self._sched.discard(name):
                        _resolve_future(r.future, error=err)
            elif not drain:
                self._fail_pending_stopped()

    def _fail_pending_stopped(self) -> None:
        """``stop(drain=False)``: discard every queued request and fail its
        future with typed :class:`ServerStoppedError`, so no waiter is
        left blocked on a future nothing will ever resolve."""
        for name in list(self.pending()):
            err = ServerStoppedError(
                f"server stopped (drain=False) with {name!r} requests "
                "pending — the request was not served; resubmit after "
                "start() if still wanted")
            for r in self._sched.discard(name):
                _resolve_future(r.future, error=err)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def __enter__(self) -> "AsyncMultiModelServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()

    # -- ingestion ----------------------------------------------------------

    def _typed_future(self, req: InferRequest, raw: Future) -> Future:
        """Wrap a raw-output future into one resolving to
        :class:`InferResult` (errors/cancellation pass through)."""
        out: Future = Future()

        def _done(f: Future) -> None:
            if f.cancelled():
                out.cancel()
                return
            exc = f.exception()
            if exc is not None:
                _resolve_future(out, error=exc)
            else:
                _resolve_future(out, result=InferResult(
                    req.model, f.result(), req.flows,
                    queue_wait_ms=getattr(f, "queue_wait_ms", None)))

        raw.add_done_callback(_done)
        return out

    def submit(self, request, *legacy_inputs, timeout: float | None = None,
               deadline_ms: float | None = None) -> Future:
        """Thread-safe enqueue of one :class:`InferRequest`; returns a
        :class:`concurrent.futures.Future` of its :class:`InferResult`
        (the legacy ``submit(name, *inputs, deadline_ms=...)`` shape still
        works as a deprecated shim whose future resolves to the raw np
        output, as before). Parameters and failure modes as
        :meth:`MultiModelServer.submit` (``timeout`` in seconds for
        ``block`` backpressure), with one difference in how deadline
        misses surface: a shed or admission-refused request FAILS THE
        RETURNED FUTURE with :class:`DeadlineExceededError` instead of
        raising here (uniform handling at ``future.result()`` whether the
        miss was predicted at submit or happened in the queue). Dispatch
        errors also ride on the future — async requests are never
        requeued."""
        fut: Future = Future()
        if isinstance(request, InferRequest):
            if legacy_inputs or deadline_ms is not None:
                raise TypeError(
                    "submit(InferRequest) takes no extra inputs or "
                    "deadline_ms — they ride in the request")
            try:
                self._enqueue(request.model, request.inputs, fut, timeout,
                              deadline_ms=request.deadline_ms,
                              priority=request.priority)
            except DeadlineExceededError as e:
                _resolve_future(fut, error=e)
            return self._typed_future(request, fut)
        _warn_legacy("AsyncMultiModelServer.submit(name, *inputs)",
                     "pass an InferRequest")
        try:
            self._enqueue(request, legacy_inputs, fut, timeout,
                          deadline_ms=deadline_ms)
        except DeadlineExceededError as e:
            _resolve_future(fut, error=e)
        return fut

    async def infer_async(self, request, *legacy_inputs,
                          timeout: float | None = None,
                          deadline_ms: float | None = None):
        """asyncio-native single request: ``await`` the
        :class:`InferResult` for one :class:`InferRequest` from a running
        event loop without blocking it (the legacy ``infer_async(name,
        *inputs)`` shape awaits the raw output, deprecated).

        The enqueue itself runs in a worker thread
        (``asyncio.to_thread``) because ``policy="block"`` backpressure
        can park the submitter; the returned future is then awaited via
        ``asyncio.wrap_future``. Parameters as :meth:`submit`. Raises
        :class:`DeadlineExceededError` if the request is refused at
        admission or shed in the queue, ``RuntimeError`` if the drain loop
        is not running (the await would never complete)."""
        if not self.running:
            raise RuntimeError(
                "the background drain loop is not running — start() the "
                "server (or use it as a context manager) before "
                "infer_async(), otherwise the await would never resolve")
        fut = await asyncio.to_thread(
            self.submit, request, *legacy_inputs,
            timeout=timeout, deadline_ms=deadline_ms)
        return await asyncio.wrap_future(fut)

    def serve(self, requests, *, backend: str | None = None) -> list:
        """Mixed-request convenience over futures: submits everything —
        a list of :class:`InferRequest` returning :class:`InferResult` per
        request, or legacy ``(name, inputs[, deadline_ms])`` tuples
        returning raw outputs (deprecated) — and waits for the results in
        order. Unlike the sync server there is no partial-result exception
        — each future fails independently (sheds carry
        :class:`DeadlineExceededError`), so this raises the FIRST failed
        request's error once all are settled."""
        if backend is not None:
            raise ValueError(
                "AsyncMultiModelServer.serve dispatches via the background "
                "loop; per-call backend overrides are a sync-drain feature "
                "(register the model with the backend you want instead)")
        if not self.running:
            raise RuntimeError(
                "the background drain loop is not running — start() the "
                "server (or use it as a context manager) before serve(), "
                "otherwise the submitted futures would never resolve")
        reqs, typed = _as_requests(requests, named=True)
        if not typed:
            _warn_legacy("AsyncMultiModelServer.serve(list of (name, "
                         "inputs) tuples)", "pass a list of InferRequest")
        futs = [self.submit(req) for req in reqs]   # always the typed path
        # settle EVERYTHING before raising (the documented contract): an
        # early failure must not leave later requests in flight while the
        # caller proceeds to resubmit/stop/inspect
        concurrent.futures.wait(futs)
        if not typed:
            return [f.result().output for f in futs]
        return [f.result() for f in futs]

    # -- the background loop ------------------------------------------------

    def _serve_loop(self) -> None:
        # claim the dispatch edge for this thread: under PEGASUS_SANITIZE=1
        # any dispatch from another thread while the loop runs raises
        # ThreadAffinityError (release on exit so stop() + sync drain()
        # stragglers stay legal)
        self._dispatch_affinity.bind()
        try:
            self._serve_loop_body()
        finally:
            self._dispatch_affinity.release()

    def _serve_loop_body(self) -> None:
        while not self._stop_flag.is_set():
            try:
                self._round += 1
                with TraceAnnotation("serve.round", round=self._round):
                    # re-read per round: server.quantum is documented as a
                    # live tunable, so the loop must not cache it at thread
                    # start. Models inside their retry backoff window are
                    # excluded — their requeued-at-front work waits out the
                    # pause while every other model keeps draining.
                    now = time.perf_counter()
                    backoff = frozenset(
                        n for n, t in self._retry_not_before.items()
                        if t > now)
                    groups = self._sched.pull_round(self._quantum(),
                                                    exclude=backoff)
                    # two-phase like drain(): enqueue every model's chunks
                    # on the device before blocking on any result. Async
                    # failures land on the futures, never requeue — a
                    # poisoned request must not wedge the loop forever.
                    begun = [self._begin_group(name, reqs, None)
                             for name, reqs in groups]
                    for g in begun:
                        try:
                            self._finish_group(g)
                        except Exception as e:   # unexpected: _finish_group
                            # already routes dispatch errors onto futures,
                            # so anything escaping it would otherwise
                            # strand this group's futures AND skip every
                            # later group's
                            self.loop_errors.append(e)
                            for r in g["reqs"]:
                                _resolve_future(r.future, error=e)
                if not groups:
                    with TraceAnnotation("sched.wait"):
                        if backoff:
                            # wait_for_work returns immediately while the
                            # backed-off work sits queued; pace the retry
                            # loop instead of spinning on it
                            time.sleep(0.002)
                        else:
                            self._sched.wait_for_work(self._idle_wait)
            except Exception as e:               # pragma: no cover - safety
                self.loop_errors.append(e)
                time.sleep(self._idle_wait)


def mlp_b_server(backends=("onehot",), *, seed: int = 0, **server_kw):
    """The MLP-B serving recipe: the model of
    :func:`repro.nets.mlp.mlp_b_recipe` registered once per backend, as
    ``"mlp_b/<backend>"``, on an always-on :class:`AsyncMultiModelServer`
    (``server_kw`` goes to its constructor: ``fuse``, ``devices``, ...).
    Returns ``(server, dataset)``; the server is not started yet."""
    from repro.nets.mlp import mlp_b_recipe

    banks, ds = mlp_b_recipe(seed)
    server = AsyncMultiModelServer(**server_kw)
    for be in backends:
        server.add_model(f"mlp_b/{be}", banks, backend=be)
    return server, ds


def _pegasus_demo(args) -> None:
    """--pegasus: build MLP-B (:func:`mlp_b_server`) and serve request
    batches on the chosen backend through the always-on async server."""
    server, ds = mlp_b_server([args.backend], fuse=not args.no_fuse)
    name = f"mlp_b/{args.backend}"
    st0 = server.stats()["engine"]["models"][name]
    print(f"plan compiled in {st0['plan_build_ms']:.1f} ms "
          f"({st0['num_banks']} banks, {st0['fused_groups']} fused "
          f"groups covering {st0['fused_banks']} banks, backend={args.backend})")
    x = ds.test["stats"].astype(np.float32)
    requests = [InferRequest(name, x[i : i + args.batch])
                for i in range(0, min(len(x), 8 * args.batch), args.batch)]
    with server:
        server.serve(requests)  # warmup/compile
        t0 = time.perf_counter()
        results = server.serve(requests)   # outputs arrive as np arrays
        dt = time.perf_counter() - t0
    flows = sum(r.flows for r in results)
    print(f"served {len(requests)} requests ({flows} flows) in {dt * 1e3:.1f} ms "
          f"→ {flows / dt:.0f} flows/s on backend={args.backend}")
    st = server.stats()["engine"]["models"][name]
    print(f"compile cache: {st['traces']} traces, {st['bucket_hits']} bucket "
          f"hits over {st['jit_calls']} jit calls; buckets={st['buckets']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pegasus", action="store_true",
                    help="serve a pegasusified model via the execution engine")
    ap.add_argument("--backend", default="onehot",
                    choices=["gather", "onehot", "kernel", "kernel_q8"],
                    help="engine backend bound to the serving plan")
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable cross-bank primitive fusion (A/B escape "
                         "hatch; fusion is the default)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.pegasus:
        _pegasus_demo(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --pegasus is given")
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    server = Server(cfg, mesh, batch_size=args.batch)
    prompts = np.ones((args.batch, 1), np.int32)
    t0 = time.perf_counter()
    out = server.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.max_new / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
