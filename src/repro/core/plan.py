"""StencilPlan — cached, hashable execution plans for the melt engine.

Serving-oriented amortization (ROADMAP: "serve heavy traffic"): deriving the
:class:`~repro.core.grid.QuasiGrid` and retracing/compiling the stencil body
are pure per-*shape* costs, yet ``apply_stencil`` used to pay them per call.
A :class:`StencilPlan` captures everything static about one stencil problem —

    (input shape, dtype, op_shape, stride, padding, dilation,
     normalized pad_value, execution path, batched?)

— together with its derived ``QuasiGrid`` and a jitted executor, in a
process-wide cache.  Repeated calls with the same signature skip grid
derivation and XLA retracing entirely: dispatch is one dict lookup plus a
jit cache hit (DESIGN.md §7).

The cache is LRU-bounded (``PLAN_CACHE_CAPACITY`` plans): each plan pins a
compiled executor, so a server fed ragged shapes must not accumulate them
forever.  Eviction drops the plan and its executor together; a re-request
simply rebuilds (one miss).

``pad_value`` is normalized at plan construction (``0`` ≡ ``0.0``; strings
must be known ``jnp.pad`` modes), so downstream paths never compare a
possibly-string value against floats.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.grid import (
    QuasiGrid,
    make_quasi_grid,
    normalize_pad_value,
    normalize_tuple,
)
from repro.obs.trace import TRACER as _TRACER, span as _span
from repro.runtime import compile_cache as _compile_cache

# count the compiles of plans' first dispatches (and the tuner's)
_compile_cache.install()

__all__ = [
    "ExecOptions",
    "StencilPlan",
    "BankPlan",
    "StatsPlan",
    "PipePlan",
    "TilePlan",
    "TunePlan",
    "get_plan",
    "get_bank_plan",
    "get_stats_plan",
    "get_pipe_plan",
    "get_tile_plan",
    "get_tune_plan",
    "normalize_axes",
    "separable_eligible",
    "plan_cache_stats",
    "plan_cached",
    "plan_cache_reset",
    "clear_plan_cache",
    "plan_fingerprint",
    "METHODS",
]

#: every accepted ``method=`` spelling, in the order shown in errors
METHODS = ("auto", "materialize", "lax", "fused")

#: max resident plans; each pins one jitted executor (compiled computation)
PLAN_CACHE_CAPACITY = 256

_CACHE: "OrderedDict[tuple, StencilPlan]" = OrderedDict()
_LOCK = threading.Lock()
_GLOBAL = {"hits": 0, "misses": 0, "evictions": 0}
#: per-key once-build latches: the first caller to miss a key builds it;
#: concurrent callers for the *same* key wait on its Event instead of
#: tracing a duplicate plan (the cold-plan-stampede guard the serving
#: tier relies on, DESIGN.md §15)
_BUILDING: Dict[tuple, threading.Event] = {}


def resolve_method(method: str) -> str:
    if method == "auto":
        return "fused" if jax.default_backend() == "tpu" else "lax"
    if method not in ("materialize", "lax", "fused"):
        raise ValueError(
            f"unknown method {method!r}; valid choices: "
            f"{', '.join(METHODS)}")
    return method


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    """The one validated bundle of execution kwargs every entry point shares.

    Construction (via :meth:`make`) *rejects* bad values with actionable
    messages instead of letting them fall through to a backend default:

    - ``method``     — one of :data:`METHODS`; misspellings raise with the
      full list of valid choices.
    - ``pad_value``  — normalized through
      :func:`repro.core.grid.normalize_pad_value` (``0`` ≡ ``0.0``; strings
      must be known ``jnp.pad`` modes).
    - ``batched``    — coerced to bool.
    - ``out_dtype``  — ``None`` (keep the path's native dtype) or any
      ``jnp.dtype`` spelling, canonicalized to the dtype *name* so options
      hash into plan keys.

    Instances are frozen and hashable — a plan key can embed one directly.
    Normalization runs in ``__post_init__``, so *direct* construction is
    exactly as validated as :meth:`make`: a cached plan's stored options
    can never hold a mutable or non-canonical value (a numpy ``pad_value``
    array would otherwise alias the caller's buffer — mutating it after
    plan build would silently change what the cache serves to every later
    request hashing to the same key).
    """

    method: str = "auto"
    pad_value: object = 0.0
    batched: bool = False
    out_dtype: object = None

    def __post_init__(self):
        if not isinstance(self.method, str) or self.method not in METHODS:
            raise ValueError(
                f"unknown method {self.method!r}; valid choices: "
                f"{', '.join(METHODS)}")
        # frozen dataclass: normalized values go in via object.__setattr__
        object.__setattr__(self, "pad_value",
                           normalize_pad_value(self.pad_value))
        object.__setattr__(self, "batched", bool(self.batched))
        if self.out_dtype is not None:
            try:
                object.__setattr__(self, "out_dtype",
                                   jnp.dtype(self.out_dtype).name)
            except TypeError as e:
                raise ValueError(
                    f"out_dtype {self.out_dtype!r} is not a dtype: "
                    f"{e}") from None

    @classmethod
    def make(cls, method: str = "auto", pad_value=0.0, batched: bool = False,
             out_dtype=None) -> "ExecOptions":
        return cls(method=method, pad_value=pad_value, batched=batched,
                   out_dtype=out_dtype)

    @property
    def resolved_method(self) -> str:
        """The backend-resolved execution path (``auto`` → lax/fused)."""
        return resolve_method(self.method)

    def key(self) -> tuple:
        """Hashable signature fragment (method pre-resolved)."""
        return (self.resolved_method, self.pad_value, self.batched,
                self.out_dtype)


def separable_eligible(rank: int, stride, padding: str,
                       pad_value=0.0) -> bool:
    """Whether a bank *may* run as successive 1-D passes (exactness gate).

    Separable execution rewrites one rank-k pass into k 1-D passes; the
    rewrite is exact for stride-1 'same' grids under zero / edge / reflect
    padding (those commute with per-dim passes).  A *nonzero* constant
    fill does not: the dense pass sees the raw constant in every corner
    neighbourhood, while a second 1-D pass would re-inject it over
    already-filtered boundary values — so nonzero constants stay dense.
    Rank-1 banks gain nothing — the dense pass already is 1-D.
    """
    pv = normalize_pad_value(pad_value)
    return (rank >= 2 and padding == "same"
            and tuple(stride) == (1,) * rank
            and (isinstance(pv, str) or pv == 0.0))


def separable_profitable(op_shape) -> bool:
    """Whether the 1-D rewrite is expected to *win* (cost gate for 'auto').

    Dense work per grid point is Πkᵢ taps; separable is Σkᵢ taps across
    ``rank`` extra pass dispatches.  Measured on both the fused and lax
    paths, the crossover sits near Πkᵢ ≈ 4·Σkᵢ (3³=27 vs 36: dense wins;
    5³=125 vs 60 and 9²=81 vs 72: separable wins 1.5–50x).  ``auto`` only
    factors past that ratio; ``separable=True`` forces the rewrite.
    """
    op_shape = tuple(int(k) for k in op_shape)
    numel = 1
    for k in op_shape:
        numel *= k
    return numel >= 4 * sum(op_shape)


def _plan_kind(key: tuple) -> str:
    """Which plan family a cache key belongs to (for the per-kind stats
    breakdown).  Non-stencil kinds tag key[0] with a string; bare stencil
    keys start with the input-shape tuple."""
    tag = key[0]
    if tag == "tiled":
        return "tile"
    if tag in ("bank", "stats", "pipe", "tune"):
        return tag
    return "stencil"


def _intern(key: tuple, build):
    """Lock/build/insert dance shared by every plan kind.

    The build runs outside the lock (tracing can be slow), guarded by a
    per-key once-build latch: under concurrent misses for the *same*
    key, exactly one caller builds while the others wait on the key's
    Event and then take the cache hit — a cold-plan stampede costs one
    trace, not N (DESIGN.md §15).  If the build raises, the latch is
    released and a waiter retries (becoming the builder itself), so a
    transient build failure never wedges the key.
    """
    while True:
        with _LOCK:
            plan = _CACHE.get(key)
            if plan is not None:
                _CACHE.move_to_end(key)
                plan._hits += 1
                _GLOBAL["hits"] += 1
                return plan
            ev = _BUILDING.get(key)
            if ev is None:
                ev = _BUILDING[key] = threading.Event()
                break  # this thread builds
        ev.wait()  # another thread is building this key; take its result
    try:
        with _span("plan/build", kind=_plan_kind(key)):
            plan = build()
    except BaseException:
        with _LOCK:
            _BUILDING.pop(key, None)
        ev.set()
        raise
    with _LOCK:
        _CACHE[key] = plan
        _GLOBAL["misses"] += 1
        while len(_CACHE) > PLAN_CACHE_CAPACITY:
            _CACHE.popitem(last=False)  # least-recently used
            _GLOBAL["evictions"] += 1
        _BUILDING.pop(key, None)
    ev.set()
    return plan


def _cold_call(plan, *args):
    """A plan's first dispatch: it pays trace + compile, not just a jit
    hit, and owns those compiles (``repro.runtime.compile_cache``)."""
    with _compile_cache.owned(plan.kind), \
            _span("plan/exec", kind=plan.kind, cold=True):
        return plan._exec(*args)


def plan_cached(key: tuple):
    """The resident plan for ``key`` (or ``None``), without touching LRU
    order or counters — the serving tier's warm/cold probe (a cold key
    admits under the stampede policy; a warm one dispatches immediately)
    and its warm-dispatch fast path (calling the probed plan skips the
    per-call option/key re-derivation of the full run entry points)."""
    with _LOCK:
        return _CACHE.get(key)


class StencilPlan:
    """One fully-specified stencil problem and its cached jitted executor.

    Instances are created through :func:`get_plan` (which interns them in the
    process-wide cache) and are callable: ``plan(x, weights)``.  Weights are
    a traced argument, so varying weights never retraces; only a new shape /
    dtype / geometry yields a new plan.
    """

    __slots__ = (
        "key", "in_shape", "op_shape", "stride", "padding", "dilation",
        "pad_value", "method", "dtype", "batched", "grid",
        "_exec", "_hits", "_calls", "_traces", "_count_lock",
    )

    def __init__(self, key: tuple, in_shape, op_shape, stride, padding,
                 dilation, pad_value, method, dtype, batched, grid: QuasiGrid):
        self.key = key
        self.in_shape = in_shape
        self.op_shape = op_shape
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.pad_value = pad_value
        self.method = method
        self.dtype = dtype
        self.batched = batched
        self.grid = grid
        self._hits = 0
        self._calls = 0
        self._traces = 0
        # per-plan counter guard: `n += 1` is a read-modify-write that
        # loses increments under concurrent serving threads
        self._count_lock = threading.Lock()
        self._exec = self._build_executor()

    # -- identity ----------------------------------------------------------
    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, StencilPlan) and self.key == other.key

    def __repr__(self):
        return (f"StencilPlan(in_shape={self.in_shape}, op={self.op_shape}, "
                f"method={self.method!r}, batched={self.batched}, "
                f"dtype={self.dtype})")

    # -- execution ---------------------------------------------------------
    def _build_executor(self):
        from repro.core import engine  # deferred: engine imports this module

        grid, pad_value = self.grid, self.pad_value
        method, batched = self.method, self.batched

        def run(x, weights):
            # Python side effect fires only while tracing — this IS the
            # retrace counter asserted by tests/test_plan_cache.py.
            with self._count_lock:
                self._traces += 1
            return engine.execute_stencil(
                x, grid, weights, pad_value, method, batched
            )

        return jax.jit(run)

    #: plan family tag carried into ``plan/exec`` span attrs
    kind = "stencil"

    def __call__(self, x: jax.Array, weights: jax.Array) -> jax.Array:
        with self._count_lock:
            self._calls += 1
        if self._traces == 0:
            return _cold_call(self, x, weights)
        if not _TRACER.enabled:
            return self._exec(x, weights)
        with _span("plan/exec", kind=self.kind, cold=False):
            return self._exec(x, weights)

    def stats(self) -> Dict[str, int]:
        """Per-plan counters: cache ``hits``, executor ``calls``, ``traces``."""
        return {"hits": self._hits, "calls": self._calls,
                "traces": self._traces}


def get_plan(
    in_shape: Tuple[int, ...],
    dtype,
    op_shape,
    stride=1,
    padding: str = "same",
    dilation=1,
    pad_value=0.0,
    method: str = "auto",
    batched: bool = False,
) -> StencilPlan:
    """Return the interned plan for this stencil signature (building it once).

    ``in_shape`` is the *full* input shape — leading batch dim included when
    ``batched`` — so each batch size owns one plan and one traced executor.
    """
    in_shape = tuple(int(s) for s in in_shape)
    spatial = in_shape[1:] if batched else in_shape
    rank = len(spatial)
    op_t = normalize_tuple(op_shape, rank, "op_shape")
    stride_t = normalize_tuple(stride, rank, "stride")
    dil_t = normalize_tuple(dilation, rank, "dilation")
    pv = normalize_pad_value(pad_value)
    meth = resolve_method(method)
    dt = jnp.dtype(dtype).name
    key = (in_shape, op_t, stride_t, padding, dil_t, pv, meth, dt, batched)

    def build():
        grid = make_quasi_grid(spatial, op_t, stride_t, padding, dil_t)
        return StencilPlan(key, in_shape, op_t, stride_t, padding, dil_t, pv,
                           meth, dt, batched, grid)

    return _intern(key, build)


class BankPlan(StencilPlan):
    """A :class:`StencilPlan` for an operator *bank* (DESIGN.md §9).

    The executor takes a (numel, K) weight matrix — or, when ``separable``,
    the tuple of per-dim (kᵢ, K) factor matrices — as the traced argument;
    varying weights never retraces.  ``K`` and ``separable`` are part of the
    plan key: a (shape, op, K) signature interns one jitted executor.
    """

    __slots__ = ("K", "separable")
    kind = "bank"

    def __init__(self, key, in_shape, op_shape, stride, padding, dilation,
                 pad_value, method, dtype, batched, grid, K: int,
                 separable: bool):
        self.K = K
        self.separable = separable
        super().__init__(key, in_shape, op_shape, stride, padding, dilation,
                         pad_value, method, dtype, batched, grid)

    def __repr__(self):
        return (f"BankPlan(in_shape={self.in_shape}, op={self.op_shape}, "
                f"K={self.K}, separable={self.separable}, "
                f"method={self.method!r}, batched={self.batched})")

    def _build_executor(self):
        from repro.core import engine  # deferred: engine imports this module

        grid, pad_value = self.grid, self.pad_value
        method, batched = self.method, self.batched
        if self.separable:
            def run(x, factors):
                with self._count_lock:
                    self._traces += 1
                return engine.execute_separable_bank(
                    x, grid, factors, pad_value, method, batched
                )
        else:
            def run(x, weight_matrix):
                with self._count_lock:
                    self._traces += 1
                return engine.execute_stencil_bank(
                    x, grid, weight_matrix, pad_value, method, batched
                )

        return jax.jit(run)


def get_bank_plan(
    in_shape: Tuple[int, ...],
    dtype,
    op_shape,
    stride=1,
    padding: str = "same",
    dilation=1,
    pad_value=0.0,
    method: str = "auto",
    batched: bool = False,
    K: int = 1,
    separable: bool = False,
) -> BankPlan:
    """Interned plan for a K-operator bank signature.

    Same normalization as :func:`get_plan`; the key additionally carries
    ``K`` and the separable/dense execution choice (the two run different
    executors over different weight pytrees).
    """
    in_shape = tuple(int(s) for s in in_shape)
    spatial = in_shape[1:] if batched else in_shape
    rank = len(spatial)
    op_t = normalize_tuple(op_shape, rank, "op_shape")
    stride_t = normalize_tuple(stride, rank, "stride")
    dil_t = normalize_tuple(dilation, rank, "dilation")
    pv = normalize_pad_value(pad_value)
    meth = resolve_method(method)
    dt = jnp.dtype(dtype).name
    key = ("bank", in_shape, op_t, stride_t, padding, dil_t, pv, meth, dt,
           batched, int(K), bool(separable))

    def build():
        grid = make_quasi_grid(spatial, op_t, stride_t, padding, dil_t)
        return BankPlan(key, in_shape, op_t, stride_t, padding, dil_t, pv,
                        meth, dt, batched, grid, int(K), bool(separable))

    return _intern(key, build)


def normalize_axes(ndim: int, axis, batched: bool = False
                   ) -> Tuple[int, ...]:
    """Canonicalize a reduce-axes spec to a sorted tuple of positive ints.

    ``axis=None`` means all axes; ``batched=True`` withholds dim 0 from a
    ``None`` reduction (the leading dim is a stack of independent tensors)
    and rejects reducing over it explicitly.  Pure shape math, shared by the
    stats engine and the distributed combiners so axis keys hash one way.
    """
    if axis is None:
        axes = tuple(range(1 if batched else 0, ndim))
    else:
        raw = ((int(axis),) if isinstance(axis, (int, np.integer))
               else tuple(int(a) for a in axis))
        if any(not -ndim <= a < ndim for a in raw):
            raise ValueError(f"reduce axes {axis!r} out of range for "
                             f"ndim={ndim}")
        axes = tuple(a % ndim for a in raw)
    if len(axes) != len(set(axes)):
        raise ValueError(f"duplicate reduce axes in {axis!r}")
    axes = tuple(sorted(axes))
    if not axes:
        raise ValueError("must reduce over at least one axis")
    if batched and 0 in axes:
        raise ValueError("batched=True keeps dim 0; it cannot be reduced")
    return axes


class StatsPlan:
    """Interned executor for one streaming-moments problem (DESIGN.md §10).

    A stats signature is ``(in_shape, dtype, reduce-axes, resolved path)``;
    the executor maps an array to a
    :class:`~repro.stats.moments.MomentState` pytree of mergeable
    sufficient statistics.  Shares the process-wide LRU plan cache (and its
    hit/trace counters) with stencil and bank plans — streaming stats are
    served by the same amortization machinery as filtering.
    """

    __slots__ = ("key", "in_shape", "axes", "dtype", "method", "order",
                 "_exec", "_hits", "_calls", "_traces", "_count_lock")

    def __init__(self, key: tuple, in_shape, axes, dtype, method, order):
        self.key = key
        self.in_shape = in_shape
        self.axes = axes
        self.dtype = dtype
        self.method = method
        self.order = order
        self._hits = 0
        self._calls = 0
        self._traces = 0
        self._count_lock = threading.Lock()
        self._exec = self._build_executor()

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, StatsPlan) and self.key == other.key

    def __repr__(self):
        return (f"StatsPlan(in_shape={self.in_shape}, axes={self.axes}, "
                f"method={self.method!r}, dtype={self.dtype})")

    def _build_executor(self):
        # deferred: stats imports us; importlib because the package re-exports
        # a `moments` *function* that shadows the submodule attribute
        import importlib

        _moments = importlib.import_module("repro.stats.moments")
        axes, method, order = self.axes, self.method, self.order

        def run(x):
            with self._count_lock:
                self._traces += 1
            return _moments.execute_moments(x, axes, method, order)

        return jax.jit(run)

    kind = "stats"

    def __call__(self, x: jax.Array):
        with self._count_lock:
            self._calls += 1
        if self._traces == 0:
            return _cold_call(self, x)
        if not _TRACER.enabled:
            return self._exec(x)
        with _span("plan/exec", kind=self.kind, cold=False):
            return self._exec(x)

    def stats(self) -> Dict[str, int]:
        return {"hits": self._hits, "calls": self._calls,
                "traces": self._traces}


def get_stats_plan(
    in_shape: Tuple[int, ...],
    dtype,
    axis=None,
    method: str = "auto",
    batched: bool = False,
    order: int = 4,
) -> StatsPlan:
    """Interned plan for a streaming-moments signature.

    ``axis``/``batched`` follow :func:`normalize_axes`; two spellings of the
    same reduction (``axis=None, batched=True`` vs ``axis=(1, 2)`` on rank
    3) intern one plan.  ``order`` (2 or 4) is part of the key — the
    variance fast path traces a different reduction body.
    """
    in_shape = tuple(int(s) for s in in_shape)
    axes = normalize_axes(len(in_shape), axis, batched)
    meth = resolve_method(method)
    if order not in (2, 4):
        raise ValueError(f"order must be 2 or 4, got {order}")
    dt = jnp.dtype(dtype).name
    key = ("stats", in_shape, axes, meth, dt, int(order))

    def build():
        return StatsPlan(key, in_shape, axes, dt, meth, int(order))

    return _intern(key, build)


class PipePlan:
    """Interned executor for one fused *pipeline* (DESIGN.md §11).

    A pipe signature is ``(in_shape, dtype, ExecOptions, op-chain)``; the
    planner (``repro.pipe.fuse``) has already merged composable linear
    stages and fused trailing reductions by the time a :class:`PipePlan` is
    built, so the executor runs the minimum number of melt passes.  The
    plan records that structure for inspection/tests:

    - ``passes``      — logical data traversals (fused groups; a reduction
      fused into its producer costs 0 extra).
    - ``melt_calls``  — the exact ``melt()`` count the *materialize* path
      pays (separable groups pay one 1-D melt per dim); lax/fused pay 0.

    Shares the process-wide LRU plan cache and its counters with every
    other plan kind — a pipeline is served by the same amortization
    machinery as a single stencil.
    """

    __slots__ = ("key", "in_shape", "dtype", "opts", "steps", "passes",
                 "melt_calls", "_exec", "_hits", "_calls", "_traces",
                 "_count_lock")

    def __init__(self, key: tuple, in_shape, dtype, opts: ExecOptions,
                 steps, passes: int, melt_calls: int, run_fn):
        self.key = key
        self.in_shape = in_shape
        self.dtype = dtype
        self.opts = opts
        self.steps = steps
        self.passes = passes
        self.melt_calls = melt_calls
        self._hits = 0
        self._calls = 0
        self._traces = 0
        self._count_lock = threading.Lock()

        def run(x):
            with self._count_lock:
                self._traces += 1  # fires only while tracing
            return run_fn(x)

        self._exec = jax.jit(run)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, PipePlan) and self.key == other.key

    def __repr__(self):
        return (f"PipePlan(in_shape={self.in_shape}, steps={len(self.steps)},"
                f" passes={self.passes}, method={self.opts.method!r}, "
                f"batched={self.opts.batched})")

    kind = "pipe"

    def __call__(self, x: jax.Array):
        with self._count_lock:
            self._calls += 1
        if self._traces == 0:
            return _cold_call(self, x)
        if not _TRACER.enabled:
            return self._exec(x)
        with _span("plan/exec", kind=self.kind, cold=False):
            return self._exec(x)

    def stats(self) -> Dict[str, int]:
        return {"hits": self._hits, "calls": self._calls,
                "traces": self._traces}


def get_pipe_plan(key: tuple, build) -> PipePlan:
    """Intern a pipeline plan under ``("pipe", *key)`` in the shared cache.

    The graph front end (``repro.pipe.compile``) supplies both the
    signature and the builder; this indirection keeps ``core.plan`` free of
    a ``repro.pipe`` import while pipelines still share the one LRU cache
    (and its hit/miss/eviction counters) with stencil/bank/stats plans.
    """
    return _intern(("pipe",) + tuple(key), build)


class TilePlan(PipePlan):
    """A :class:`PipePlan` specialized to one *tile-shape class* of an
    out-of-core run (DESIGN.md §12).

    A tiled execution streams many tiles through few plans: every tile
    whose geometry class — patch shape, boundary-pad widths, alignment and
    crop — matches an interned ``TilePlan`` reuses its jitted executor, so
    the trace count scales with the number of classes (≤ 3 per dim for
    uniform tilings: first / interior / last), never with the number of
    tiles.  ``spec`` keeps the class geometry inspectable;
    ``tile_batch`` > 0 marks the stacked variant that executes a whole
    same-class tile group in one (optionally mesh-sharded) dispatch.

    The crop to the tile's output box and the ``out_dtype`` cast are fused
    *inside* the jitted executor (only final bytes ever cross the
    device→host bus), so the plan also records the fused result's
    ``out_shape``/``out_dtype`` — the assemble path sizes its staged
    writeback from this metadata instead of inspecting a computed tile
    (``None`` for reduction-terminated programs, whose result is a merge
    state, not an array).
    """

    __slots__ = ("spec", "tile_batch", "out_shape", "out_dtype")
    kind = "tile"

    def __init__(self, key, in_shape, dtype, opts, steps, passes, melt_calls,
                 run_fn, spec=None, tile_batch: int = 0, out_shape=None,
                 out_dtype=None):
        self.spec = spec
        self.tile_batch = tile_batch
        self.out_shape = tuple(out_shape) if out_shape is not None else None
        self.out_dtype = out_dtype
        super().__init__(key, in_shape, dtype, opts, steps, passes,
                         melt_calls, run_fn)

    def __repr__(self):
        return (f"TilePlan(patch={self.in_shape}, steps={len(self.steps)}, "
                f"tile_batch={self.tile_batch}, out={self.out_shape}, "
                f"method={self.opts.method!r})")


def get_tile_plan(key: tuple, build) -> TilePlan:
    """Intern a tile-class plan under ``("tiled", *key)`` in the shared
    LRU cache — tiled execution is served (and evicted) by the same
    machinery as every other plan kind, and the global hit/miss counters
    are what the one-trace-per-class tests read."""
    return _intern(("tiled",) + tuple(key), build)


class TunePlan:
    """A measured kernel-tuning decision, interned like any other plan.

    Holds the winning ``tile_rows`` for one canonical kernel problem —
    keyed ``("tune", backend, family, numel, c_in, c_out, dtype)`` by
    ``repro.kernels.melt_stencil.tuned_tile_rows`` — plus the candidate
    set and per-candidate timings for inspection.  Interning in the
    shared LRU gives the tuner the plan-cache contract for free: one
    measurement per key (stampede-latched), hits thereafter, LRU
    eviction, and a ``kinds["tune"]`` row in :func:`plan_cache_stats`.
    """

    __slots__ = ("key", "tile_rows", "candidates", "timings_us", "_hits")
    kind = "tune"

    def __init__(self, key: tuple, tile_rows: int, candidates, timings_us):
        self.key = key
        self.tile_rows = int(tile_rows)
        self.candidates = tuple(candidates)
        self.timings_us = tuple(timings_us)
        self._hits = 0

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, TunePlan) and self.key == other.key

    def __repr__(self):
        pairs = ", ".join(f"{c}:{t:.0f}us" for c, t in
                          zip(self.candidates, self.timings_us))
        return f"TunePlan(tile_rows={self.tile_rows}, measured={{{pairs}}})"

    def stats(self) -> Dict[str, int]:
        return {"hits": self._hits}


def get_tune_plan(key: tuple, build) -> TunePlan:
    """Intern a kernel-tuning decision under ``("tune", *key)`` in the
    shared LRU cache — measured autotuning is served (and evicted) by the
    same machinery as every other plan kind, so a key is measured once
    per process and every later request is a cache hit."""
    return _intern(("tune",) + tuple(key), build)


def plan_fingerprint(*parts) -> str:
    """Stable hex digest of a nested plan-key structure.

    In-process plan keys only need to be hashable; a *checkpoint* key
    must additionally be stable across processes, so equality can gate
    resuming a journaled stream against the plan that wrote it
    (DESIGN.md §13).  ``parts`` may nest tuples/lists/dicts of
    primitives (str/int/float/bool/None, numpy scalars); anything else
    falls back to ``repr`` — which keeps the digest *conservative*: a
    structure whose repr is process-dependent (e.g. an anonymous
    ``pointwise`` op keyed on ``id(fn)``) changes the fingerprint and a
    cross-process resume refuses, rather than silently mixing plans.
    Give such ops an explicit ``key=`` to make their streams resumable.
    """
    import hashlib

    def canon(o) -> str:
        if isinstance(o, (tuple, list)):
            return "(" + ",".join(canon(i) for i in o) + ")"
        if isinstance(o, dict):
            items = sorted((canon(k), canon(v)) for k, v in o.items())
            return "{" + ",".join(f"{k}:{v}" for k, v in items) + "}"
        if isinstance(o, (np.integer, np.floating, np.bool_)):
            return repr(o.item())
        if isinstance(o, float):
            return repr(o)  # repr is exact for floats (round-trips)
        return repr(o)

    return hashlib.sha256(canon(parts).encode()).hexdigest()[:24]


def plan_cache_stats() -> Dict[str, object]:
    """Process-wide counters: ``size``, ``hits``, ``misses``, ``evictions``,
    plus a per-kind resident-plan breakdown under ``"kinds"`` (how many of
    the ``size`` plans are stencil / bank / stats / pipe / tile / tune)."""
    with _LOCK:
        kinds = {"stencil": 0, "bank": 0, "stats": 0, "pipe": 0, "tile": 0,
                 "tune": 0}
        for key in _CACHE:
            kinds[_plan_kind(key)] += 1
        return {"size": len(_CACHE), **_GLOBAL, "kinds": kinds}


def plan_cache_reset() -> None:
    """Zero the global hit/miss/eviction counters, keeping resident plans.

    Tests (and ``obs``-driven A/B runs) that only need a clean counter
    baseline use this instead of :func:`clear_plan_cache` — dropping the
    plans themselves would force re-traces and re-compiles the measurement
    doesn't want to pay."""
    with _LOCK:
        for k in _GLOBAL:
            _GLOBAL[k] = 0


def clear_plan_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        for k in _GLOBAL:
            _GLOBAL[k] = 0
