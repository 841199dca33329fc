"""Rule registry + repo-specific configuration for the concurrency lint.

This module is pure data: rule IDs, the comment grammar, the declared lock
hierarchy, and the call-classification sets the AST passes in
:mod:`repro.analysis.lint` consult. Keeping it separate means the policy a
finding enforces is reviewable without reading the walker code — and the
runtime sanitizer (:mod:`repro.analysis.sanitizer`) shares the SAME
hierarchy table, so the static and dynamic checks can never disagree about
which nesting order is legal.

Comment grammar (all parsed by regex out of the token stream):

``# guarded-by: <lock>``
    On (or directly above) a ``self.<attr> = ...`` assignment: every later
    touch of ``<attr>`` anywhere in the module must happen under a ``with``
    on a lock whose attribute name matches ``<lock>`` (PG002).

``# holds: <lock>``
    On (or directly above) a ``def``: the function's contract is that the
    CALLER already holds ``<lock>`` — its body is checked as if the lock
    were held. The runtime sanitizer cannot see this contract, so it is a
    lint-only escape hatch for private helpers.

``# pegasus-lint: disable=PG001,PG004 <reason>``
    Suppress those rules on this line (or the line below, when the comment
    stands alone). The reason is MANDATORY — a bare disable is itself a
    finding (PG000).

``# pegasus-lint: disable-block=PG004 <reason>``
    Same, but on a compound statement's header line it suppresses the whole
    statement body (e.g. one justified ``with ctr.lock:`` in a traced
    forward instead of a comment per mutated counter).
"""

from __future__ import annotations

import re

RULES = {
    "PG000": "malformed suppression or annotation (disable= needs rule IDs "
             "and a written reason; guarded-by must sit on an attribute "
             "assignment)",
    "PG001": "jax dispatch, plan build, or blocking call inside a "
             "`with <lock>:` body",
    "PG002": "attribute annotated `# guarded-by: <lock>` touched without "
             "holding that lock",
    "PG003": "lock acquired against the declared hierarchy "
             "(registry -> scheduler -> counters)",
    "PG004": "impure operation inside a jitted forward / Pallas kernel "
             "body, or a donated buffer read after the jitted call",
}

# Condition variables share their underlying lock: holding or acquiring the
# condition IS holding the lock. Both the scheduler (_space/_work on _lock)
# and the device pool (_work on _lock) follow this naming.
LOCK_ALIASES = {
    "_space": "_lock",
    "_work": "_lock",
}

# The declared acquisition hierarchy, OUTER to INNER. Static form: keyed by
# (module stem, canonical lock attribute name) — PG003 checks syntactic
# nesting within one module, so each module sees only its own ranks.
# Runtime form (LOCK_RANKS): keyed by the qualified name passed to
# sanitizer.make_lock(), so the InstrumentedLock graph checks nesting
# ACROSS modules (e.g. registry.stats() holding registry._lock while
# compile_stats() takes the plan counter lock is legal: rank 0 -> rank 5).
STATIC_LOCK_ORDER = {
    ("registry", "_lock"): 0,
    ("scheduler", "_lock"): 1,
    ("serve", "_ctr_lock"): 2,
    ("devices", "_lock"): 3,
    ("plan", "_replica_lock"): 4,
    ("plan", "lock"): 5,          # _PlanCounters.lock — the innermost lock
    # self-healing layer (ISSUE 9): breaker state is queried under
    # devices._lock in stream placement (3 → 6) and never wraps another
    # lock; the injector lock only guards spec matching/counting.
    ("health", "_lock"): 6,
    ("chaos", "_lock"): 7,
}

LOCK_RANKS = {
    "registry._lock": 0,
    "scheduler._lock": 1,
    "serve._ctr_lock": 2,
    "devices._lock": 3,
    "plan._replica_lock": 4,
    "plan._ctr.lock": 5,
    "health._lock": 6,
    "chaos._lock": 7,
}

# -- PG001 classification ---------------------------------------------------

# Any call rooted at these names is jax dispatch (device transfer, tracing,
# or execution) — multi-millisecond work that must not run under a lock.
JAX_ROOTS = frozenset({"jax", "jnp"})

# Plan construction entry points: a compile under a lock stalls every
# other thread for seconds (the registry builds OUTSIDE its lock for
# exactly this reason).
PLAN_CALLS = frozenset({"build_plan", "plan_for"})

# Dotted calls that block the calling thread outright.
BLOCKING_DOTTED = frozenset({"time.sleep", "concurrent.futures.wait"})

# Final attribute names that block: thread.join() and future.result().
# (str.join on a literal separator is exempted by the walker; Condition
# .wait() is NOT listed — it releases the lock while parked, which is the
# one legitimate way to sleep under a lock.)
BLOCKING_FINAL_ATTRS = frozenset({"join", "result"})

# Receiver-sensitive blocking methods: ``.get()``/``.put()`` block only on
# queue-like receivers and ``.wait()`` only on event-like ones — dict.get
# and Condition.wait must stay exempt (the latter releases the lock while
# parked). Static analysis cannot type the receiver, so the walker matches
# the receiver's FINAL name component (case-insensitive substring) against
# these hints — the same name-convention contract the whole lint rests on
# (locks end in "lock", queues carry "queue"/"_q", events "event"/"done").
BLOCKING_RECEIVER_HINTS = {
    "get": ("queue", "inbox", "mailbox", "_q"),
    "put": ("queue", "inbox", "mailbox", "_q"),
    "wait": ("event", "evt", "done", "ready", "stopped", "barrier"),
}


def blocking_receiver(attr: str, receiver: str | None,
                      n_pos_args: int = 0) -> bool:
    """True when ``receiver.attr(...)`` matches the queue/event blocking
    table: ``queue.Queue.get/put`` and ``threading.Event.wait`` under a
    lock park the holder while every other thread spins on the lock.

    Two disambiguations keep ``dict.get`` exempt: a blocking ``Queue.get()``
    takes no positional argument (``dict.get(key)`` always does), and a
    PLURAL queue-like name (``_queues``) is a container of queues — its
    ``.get``/``.put`` are the dict's, not a queue's."""
    hints = BLOCKING_RECEIVER_HINTS.get(attr)
    if not hints or not receiver:
        return False
    if attr == "get" and n_pos_args:
        return False
    low = receiver.lower()
    if attr in ("get", "put") and low.endswith("s"):
        return False
    for h in hints:
        if h.startswith("_"):          # suffix hints: "work_q", or bare "q"
            if low == h.lstrip("_") or low.endswith(h):
                return True
        elif h in low:
            return True
    return False

# -- PG004 classification ---------------------------------------------------

# Whole-plan forwards are found three ways: by convention every structural
# forward is a local function with one of these names; by being the first
# argument of jax.jit(...); or by being the (possibly functools.partial-
# wrapped) first argument of pl.pallas_call(...).
PURE_FUNC_NAMES = frozenset({"forward", "_pure"})

# Call roots that are side-effecting / nondeterministic at trace time.
IMPURE_ROOTS = frozenset({"time", "random"})
IMPURE_DOTTED_PREFIXES = (("np", "random"), ("numpy", "random"))
IMPURE_BUILTINS = frozenset({"print", "open", "input"})

# Method names that mutate their receiver — calling one on a NONLOCAL
# object from inside a traced body is a trace-time side effect.
MUTATOR_METHODS = frozenset({
    "add", "append", "appendleft", "extend", "extendleft", "update",
    "setdefault", "pop", "popleft", "popitem", "remove", "discard",
    "clear", "insert",
})

# Roots whose attribute calls are pure array ops, never receiver mutation
# (jnp.add is addition, not set.add).
SAFE_MUTATOR_ROOTS = frozenset({"jax", "jnp", "np", "numpy", "pl",
                                "functools", "math", "lax"})

# -- PGA1xx: plan-audit policy (repro.analysis.planaudit) -------------------

# The plan auditor walks a COMPILED ExecutionPlan (banks, fused stacks,
# bucket ladder, q8 tables) instead of source text; its findings carry the
# PGA1xx namespace so lint (PG0xx) and audit reports never collide.
PGA_RULES = {
    "PGA101": "fixed-point overflow: the worst-case int32 accumulator bound "
              "of a bank's q8 tables (all groups rescaled to the finest "
              "group scale) exceeds int32 (error) or is within 2x of it "
              "(warning)",
    "PGA102": "quantization fidelity: a bank's worst-case q8 dequantization "
              "error vs its f32 LUT exceeds the configured per-group "
              "relative tolerance (stale/tampered q8 table)",
    "PGA103": "VMEM footprint: a pallas_call's worst-case working set "
              "(operand blocks + stacked tables) exceeds the per-target "
              "VMEM budget (error) or is within the margin of it (warning)",
    "PGA104": "kernel-tile alignment: a ladder bucket dispatches hidden pad "
              "rows (bucket not divisible by the batch tile)",
    "PGA105": "fusion rejection: an adjacent chained bank pair did not fuse "
              "(v/C mismatch, chaining break, nmax_cap split, fuse=False, "
              "or a family builder without the fusion pass)",
    "PGA106": "dataplane resource fit: the plan lowered to a MAT pipeline "
              "exceeds the declared switch target's SRAM/TCAM/bus/PHV "
              "budget (error); recirculation passes are a warning",
}

INT32_MAX = 2**31 - 1

# PGA101: warn when the overflow bound is within this factor of int32.
PGA101_MARGIN = 2.0

# PGA102: max per-group relative dequant error. Symmetric int8
# round-to-nearest guarantees err <= scale/2 = amax/254 (~0.4% of the
# group's amax); 1% only trips when the q8 table no longer matches the f32
# LUT it claims to quantize.
PGA102_REL_TOL = 1.0 / 100.0

# PGA103: per-core VMEM budget (bytes) and warn margin. ~16 MB/core is the
# common TPU figure; override per target via AuditConfig.
PGA103_VMEM_BUDGET = 16 * 2**20
PGA103_MARGIN = 2.0

# -- comment grammar --------------------------------------------------------

GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][\w]*)")
HOLDS_RE = re.compile(r"#\s*holds:\s*([A-Za-z_][\w]*)")
SUPPRESS_RE = re.compile(
    r"#\s*pegasus-lint:\s*(disable|disable-block)=([A-Za-z0-9,]*)\s*(.*)")


def canonical_lock(name: str) -> str | None:
    """Canonical lock name for an attribute name, or None if it is not a
    lock: condition aliases map to their lock, and anything else must end
    in ``lock`` (``_lock``, ``_ctr_lock``, ``lock``, ...)."""
    name = LOCK_ALIASES.get(name, name)
    return name if name.lower().endswith("lock") else None


def static_ranks_for_module(stem: str) -> dict[str, int]:
    """``{lock attribute name: rank}`` for one module's PG003 check."""
    return {attr: rank for (mod, attr), rank in STATIC_LOCK_ORDER.items()
            if mod == stem}
