"""jaxlint rules — each targets one way a JAX tree silently gets slow.

Every rule has a stable kebab-case id (used in ``# jaxlint:
disable=<rule>`` suppressions and baseline fingerprints), a one-line
``doc`` for ``--list-rules``, and a ``check(mod, project)`` returning
Violations. docs/ANALYSIS.md carries the full catalog with before/after
examples.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from photon_ml_tpu.analysis.core import (
    ModuleSource,
    Project,
    Violation,
    _jit_decorator_statics,
    is_jit_reference,
)

# Modules whose code runs on the device hot path: host-sync and
# dtype-drift findings here cost real dispatches / break f32 parity.
DEVICE_DIRS = (
    "photon_ml_tpu/ops/",
    "photon_ml_tpu/serving/",
    "photon_ml_tpu/optimization/",
    "photon_ml_tpu/algorithm/",
)


def _in_device_dir(mod: ModuleSource) -> bool:
    p = "/" + mod.path
    return any("/" + d in p for d in DEVICE_DIRS)


def _enclosing_scope_nodes(mod: ModuleSource, node: ast.AST) -> Set[ast.AST]:
    out: Set[ast.AST] = set()
    fi = mod.fn_of.get(node)
    while fi is not None:
        out.add(fi.node)
        fi = fi.parent
    return out


class RetraceHazardRule:
    """Per-call recompilation: the single most expensive silent failure —
    every retrace costs a full XLA compile (seconds) on what should be a
    cached microsecond dispatch."""

    id = "retrace-hazard"
    doc = ("lambda/locally-defined function in a static_argnames position, "
           "or jax.jit built inside a function and invoked without caching")

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                out += self._check_static_args(mod, project, node)
                out += self._check_per_call_jit(mod, node)
        return [v for v in out if v is not None]

    # -- (a) unstable callables in static positions ------------------------

    def _resolve_sig(self, mod: ModuleSource, project: Project,
                     call: ast.Call):
        f = call.func
        if isinstance(f, ast.Name):
            fq = mod.imports.get(f.id, f"{mod.module_name}.{f.id}")
            return project.jit_sigs.get(fq)
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            target = mod.imports.get(f.value.id)
            if target is not None:
                return project.jit_sigs.get(f"{target}.{f.attr}")
        return None

    def _unstable_callable(self, mod: ModuleSource, call: ast.Call,
                           value: ast.AST) -> Optional[str]:
        """'lambda' / 'locally-defined function <n>' when ``value`` is a
        fresh function object per call of the enclosing scope; None for
        stable references (module-level defs, attributes/bound methods —
        those hash stably for a persistent owner)."""
        if isinstance(value, ast.Lambda):
            return "a lambda"
        if isinstance(value, ast.Name):
            scopes = _enclosing_scope_nodes(mod, call)
            for fi in mod.functions:
                if fi.name == value.id and fi.parent is not None \
                        and fi.parent.node in scopes:
                    return f"locally-defined function {value.id!r}"
        return None

    def _check_static_args(self, mod: ModuleSource, project: Project,
                           call: ast.Call) -> list:
        sig = self._resolve_sig(mod, project, call)
        if sig is None:
            return []
        out = []
        for kw in call.keywords:
            if kw.arg is None or not (
                    kw.arg in sig.static_names
                    or (sig.params is not None and kw.arg in sig.params
                        and sig.params.index(kw.arg) in sig.static_nums)):
                continue
            what = self._unstable_callable(mod, call, kw.value)
            if what:
                out.append(mod.violation(
                    kw.value, self.id,
                    f"{what} passed as static arg {kw.arg!r} of "
                    f"{sig.name} (jit at {sig.where}): a fresh function "
                    "object per call defeats the jit cache — pass a "
                    "module-level function or a bound method of a "
                    "persistent object"))
        for idx, arg in enumerate(call.args):
            pname = sig.static_param_at(idx)
            if pname is None:
                continue
            what = self._unstable_callable(mod, call, arg)
            if what:
                out.append(mod.violation(
                    arg, self.id,
                    f"{what} passed as static arg {pname!r} of "
                    f"{sig.name} (jit at {sig.where}): a fresh function "
                    "object per call defeats the jit cache"))
        return out

    # -- (b) per-call jax.jit construction ---------------------------------

    def _check_per_call_jit(self, mod: ModuleSource,
                            call: ast.Call) -> list:
        if not (is_jit_reference(call.func) and mod.fn_of.get(call)):
            return []
        parent = mod.parents.get(call)
        # jax.jit(f)(x): constructed and invoked in one expression.
        if isinstance(parent, ast.Call) and parent.func is call:
            return [mod.violation(
                call, self.id,
                "jax.jit(...) constructed and called in the same "
                "expression inside a function: this retraces and "
                "recompiles on EVERY call — hoist the jit to module "
                "scope or cache the wrapped function")]
        # fn = jax.jit(f) ... fn(x), with fn never escaping the function.
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1 \
                and isinstance(parent.targets[0], ast.Name):
            name = parent.targets[0].id
            if self._only_called_locally(mod, call, name):
                return [mod.violation(
                    call, self.id,
                    f"jax.jit result {name!r} is built and called inside "
                    "this function but never cached (not returned or "
                    "stored): it recompiles on every call of the "
                    "enclosing function")]
        return []

    def _only_called_locally(self, mod: ModuleSource, call: ast.Call,
                             name: str) -> bool:
        fi = mod.fn_of.get(call)
        called = False
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Name) and node.id == name
                    and isinstance(node.ctx, ast.Load)):
                continue
            parent = mod.parents.get(node)
            if isinstance(parent, ast.Call) and parent.func is node:
                called = True
            else:
                return False  # escapes: returned / stored / passed on
        return called


class HostSyncRule:
    """Host-device synchronization inside traced code: a concretization
    of a tracer either crashes the trace or (worse) silently pins a
    value at trace time."""

    id = "host-sync"
    doc = (".item()/float()/int()/np.asarray/block_until_ready applied "
           "inside jit-reachable code in device-path modules")

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        if not _in_device_dir(mod):
            return []
        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not project.in_traced_code(mod, node):
                continue
            v = self._check_call(mod, node)
            if v is not None:
                out.append(v)
        return out

    def _static_names_of_scope(self, mod: ModuleSource,
                               node: ast.AST) -> Set[str]:
        names: Set[str] = set()
        fi = mod.fn_of.get(node)
        while fi is not None:
            if isinstance(fi.node, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                for dec in fi.node.decorator_list:
                    statics = _jit_decorator_statics(dec)
                    if statics is not None:
                        names |= statics[0]
            fi = fi.parent
        return names

    def _check_call(self, mod: ModuleSource,
                    call: ast.Call) -> Optional[Violation]:
        f = call.func
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not call.args:
                return mod.violation(
                    call, self.id,
                    ".item() in traced code forces a device->host sync "
                    "(or fails under jit) — keep the value on device, or "
                    "materialize OUTSIDE the jitted region")
            if f.attr in ("block_until_ready", "device_get"):
                return mod.violation(
                    call, self.id,
                    f".{f.attr}() in traced code is a host sync point — "
                    "move it outside the jitted region")
            if isinstance(f.value, ast.Name) \
                    and f.value.id in mod.numpy_aliases \
                    and f.attr in ("asarray", "array"):
                return mod.violation(
                    call, self.id,
                    f"np.{f.attr}(...) in traced code materializes the "
                    "operand on host — use jnp equivalents so the value "
                    "stays traced")
        elif isinstance(f, ast.Name) and f.id in ("float", "int") \
                and len(call.args) == 1 \
                and isinstance(call.args[0], ast.Name):
            arg = call.args[0].id
            if arg not in self._static_names_of_scope(mod, call):
                return mod.violation(
                    call, self.id,
                    f"{f.id}({arg}) in traced code concretizes its "
                    "operand (host sync; TracerConversionError if it is "
                    "a tracer) — use jnp.asarray/.astype, or mark "
                    f"{arg!r} static if it is a python scalar")
        return None


class DtypeDriftRule:
    """f32 parity (docs/F32_PARITY.md): device-path modules must not bake
    in float64 or rely on the x64-dependent default dtype — the same code
    must produce the same executables in the f32 and f64 CI configs."""

    id = "dtype-drift"
    doc = ("np.float64 or dtype-less jnp.array/jnp.zeros literals in "
           "device-path modules that must stay f32-parity safe")

    # constructor -> index of the positional dtype argument
    _DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2}

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        if not _in_device_dir(mod):
            return []
        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "float64" \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in (mod.numpy_aliases
                                          | mod.jnp_aliases):
                v = mod.violation(
                    node, self.id,
                    "hard-coded float64 in a device-path module breaks "
                    "the f32 parity contract — thread a dtype parameter "
                    "through instead")
                if v is not None:
                    out.append(v)
            elif isinstance(node, ast.Call):
                v = self._check_call(mod, node)
                if v is not None:
                    out.append(v)
        return out

    def _is_jnp_call(self, mod: ModuleSource, call: ast.Call,
                     attrs) -> bool:
        f = call.func
        return (isinstance(f, ast.Attribute) and f.attr in attrs
                and isinstance(f.value, ast.Name)
                and f.value.id in mod.jnp_aliases)

    @staticmethod
    def _has_float_literal(node: ast.AST) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Constant) and isinstance(n.value, float):
                return True
        return False

    def _check_call(self, mod: ModuleSource,
                    call: ast.Call) -> Optional[Violation]:
        has_dtype_kw = any(kw.arg == "dtype" for kw in call.keywords)
        if self._is_jnp_call(mod, call, self._DTYPE_POS):
            pos = self._DTYPE_POS[call.func.attr]
            if not has_dtype_kw and len(call.args) <= pos:
                return mod.violation(
                    call, self.id,
                    f"jnp.{call.func.attr}(...) without a dtype defaults "
                    "to the x64-flag-dependent float — pass the computed "
                    "dtype explicitly so f32 and f64 configs build the "
                    "same executables")
        elif self._is_jnp_call(mod, call, ("array", "asarray")):
            if not has_dtype_kw and len(call.args) == 1 \
                    and self._has_float_literal(call.args[0]):
                return mod.violation(
                    call, self.id,
                    f"jnp.{call.func.attr} of a float literal without a "
                    "dtype follows the x64 flag (f64 under x64, f32 "
                    "otherwise) — pass dtype explicitly")
        return None


class NondeterministicPytreeRule:
    """Pytree construction from unordered iteration: leaf order becomes
    part of the jit cache key, so a hash-randomized set order means
    spurious retraces across processes and unstable multihost layouts."""

    id = "nondeterministic-pytree"
    doc = ("iterating a set (or building list/tuple from one) where the "
           "resulting order can differ between processes")

    @staticmethod
    def _is_set_expr(node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in ("set", "frozenset"))

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        out: List[Violation] = []
        msg = ("iteration order of a set is not deterministic across "
               "processes — sort it (sorted(...)) before it can shape a "
               "pytree or a cache key")
        for node in ast.walk(mod.tree):
            target = None
            if isinstance(node, ast.For) and self._is_set_expr(node.iter):
                target = node.iter
            elif isinstance(node, ast.comprehension) \
                    and self._is_set_expr(node.iter):
                target = node.iter
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in ("list", "tuple") \
                    and len(node.args) == 1 \
                    and self._is_set_expr(node.args[0]):
                target = node
            if target is not None:
                v = mod.violation(target, self.id, msg)
                if v is not None:
                    out.append(v)
        return out


class TelemetryInTraceRule:
    """Telemetry belongs to the HOST loop: a span opened inside traced
    code measures trace time once and nothing on later dispatches (and a
    registry mutation there runs at trace time, not per call) — both
    silently lie. Device work is attributed at the dispatch boundary via
    the block_until_ready that already exists in host-sync code
    (docs/OBSERVABILITY.md span rules)."""

    id = "telemetry-in-trace"
    doc = ("telemetry span()/timed_span() or metric mutation "
           "(.inc()/.observe()) inside jit-reachable code")

    # photon_ml_tpu.telemetry entry points that open spans / create
    # metrics; resolved through the import table so local helpers named
    # `span` in unrelated modules do not trip the rule.
    _FACTORIES = ("span", "timed_span", "phase", "counter", "gauge",
                  "histogram")
    # Metric mutation methods — distinctive enough to flag on name alone
    # (nothing else in the tree defines .inc/.observe).
    _MUTATORS = ("inc", "observe")

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not project.in_traced_code(mod, node):
                continue
            v = self._check_call(mod, node)
            if v is not None:
                out.append(v)
        return out

    def _check_call(self, mod: ModuleSource,
                    call: ast.Call) -> Optional[Violation]:
        f = call.func
        if isinstance(f, ast.Name):
            fq = mod.imports.get(f.id, "")
            if f.id in self._FACTORIES \
                    and fq.startswith("photon_ml_tpu.telemetry"):
                return mod.violation(
                    call, self.id,
                    f"telemetry {f.id}() opened inside traced code: it "
                    "would measure trace time once and nothing per "
                    "dispatch — instrument the host loop that launches "
                    "the device work (attribute device time at an "
                    "existing block_until_ready boundary)")
        elif isinstance(f, ast.Attribute):
            if f.attr in self._MUTATORS:
                return mod.violation(
                    call, self.id,
                    f".{f.attr}() metric mutation inside traced code "
                    "runs at trace time, not per call — move it to the "
                    "host loop")
            if f.attr in self._FACTORIES and isinstance(f.value, ast.Name):
                target = mod.imports.get(f.value.id, "")
                if target.startswith("photon_ml_tpu.telemetry") \
                        or target == "photon_ml_tpu.telemetry":
                    return mod.violation(
                        call, self.id,
                        f"telemetry {f.attr}() opened inside traced "
                        "code — instrument the host loop instead")
        return None


class SpillDtypeLeakRule:
    """The shard cache's compressed spill tier (data/shard_cache.py)
    holds feature blocks as bf16 values + delta-encoded u8/u16 indices.
    Those buffers are NOT device-kernel data: a `CSRFeatures` built from
    them without the restore cast would silently jit-trace a second
    executable per bucket (dtype is part of the signature) and
    accumulate at the wrong precision — the sharded objective's kernels
    are compiled for f32/i32 (ops/sharded_objective.py, restore-dtype
    contract)."""

    id = "spill-dtype-leak"
    doc = ("spill-encoded buffers (.enc_values/.enc_cols/.enc_rows) "
           "consumed outside data/shard_cache.py's "
           "restore_spilled_features — bf16/delta data would leak into "
           "device kernels un-restored")

    #: SpillBlock's encoded fields — distinctive enough to flag on name.
    _ATTRS = ("enc_values", "enc_cols", "enc_rows")
    #: The blessed consumers, all in data/shard_cache.py: the codec
    #: pair and SpillBlock's own byte accounting.
    _ALLOWED_MODULE = "photon_ml_tpu/data/shard_cache.py"
    _ALLOWED_FNS = ("encode_spill", "restore_spilled_features", "nbytes")

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        p = "/" + mod.path
        if "/photon_ml_tpu/" not in p:
            return []  # tests/bench poke the codec fields legitimately
        allowed_module = p.endswith("/data/shard_cache.py")
        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Attribute)
                    and node.attr in self._ATTRS
                    and isinstance(node.ctx, ast.Load)):
                continue
            if allowed_module and self._in_allowed_fn(mod, node):
                continue
            v = mod.violation(
                node, self.id,
                f".{node.attr} is a spill-ENCODED buffer (bf16 values / "
                "delta-coded indices): consuming it outside "
                "data/shard_cache.py restore_spilled_features leaks "
                "non-f32 data into device kernels un-restored — "
                "restore the block through the cache's miss path "
                "instead")
            if v is not None:
                out.append(v)
        return out

    def _in_allowed_fn(self, mod: ModuleSource, node: ast.AST) -> bool:
        fi = mod.fn_of.get(node)
        while fi is not None:
            if fi.name in self._ALLOWED_FNS:
                return True
            fi = fi.parent
        return False


class BlockingInAsyncRule:
    """The serving front-end's event loop IS the product: one blocking
    call inside a coroutine stalls ADMISSION for every connected
    requester — queue-wait spikes for traffic that never touched the
    offending request. Blocking work belongs on the dispatch executor
    thread (``run_in_executor``); waits belong to ``await``."""

    id = "blocking-in-async"
    doc = ("time.sleep / block_until_ready / no-timeout queue .get() "
           "inside an async def body in serving/ — stalls the event "
           "loop for every in-flight request")

    #: Only the serving package hosts event-loop code; elsewhere a sync
    #: sleep on a worker thread is legitimate pipeline behavior. The
    #: network front door grew event loops OUTSIDE serving/ — the
    #: router CLI and the scoring driver's --listen mode run their own
    #: asyncio loops — so those modules are covered file-wise.
    _DIRS = ("photon_ml_tpu/serving/",)
    _FILES = ("photon_ml_tpu/cli/net_router.py",
              "photon_ml_tpu/cli/game_scoring_driver.py")

    def check(self, mod: ModuleSource, project: Project) -> List[Violation]:
        p = "/" + mod.path
        if not (any("/" + d in p for d in self._DIRS)
                or any(p.endswith("/" + f) for f in self._FILES)):
            return []
        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not self._in_async_body(mod, node):
                continue
            v = self._check_call(mod, node)
            if v is not None:
                out.append(v)
        return out

    @staticmethod
    def _in_async_body(mod: ModuleSource, node: ast.AST) -> bool:
        """Innermost enclosing real function (lambdas look through to
        their definer — a lambda body runs wherever it is called, and
        one defined in a coroutine usually runs there). EXCEPT a lambda
        handed straight to ``run_in_executor``/``submit``: that body
        runs on an executor thread where blocking is the whole point —
        it is the remediation this rule's messages recommend."""
        fi = mod.fn_of.get(node)
        while fi is not None and isinstance(fi.node, ast.Lambda):
            parent = mod.parents.get(fi.node)
            if isinstance(parent, ast.Call) \
                    and isinstance(parent.func, ast.Attribute) \
                    and parent.func.attr in ("run_in_executor", "submit"):
                return False
            fi = fi.parent
        return fi is not None and isinstance(fi.node, ast.AsyncFunctionDef)

    def _check_call(self, mod: ModuleSource,
                    call: ast.Call) -> Optional[Violation]:
        # An awaited call yields to the loop by construction
        # (await q.get() on an asyncio.Queue is the CORRECT pattern).
        if isinstance(mod.parents.get(call), ast.Await):
            return None
        f = call.func
        if isinstance(f, ast.Name) \
                and mod.imports.get(f.id) == "time.sleep":
            # 'from time import sleep' — same blocking call, bare name.
            return mod.violation(
                call, self.id,
                "time.sleep() inside an async def blocks the whole "
                "event loop (admission, coalescing, every pending "
                "future) — use 'await asyncio.sleep(...)'")
        if isinstance(f, ast.Attribute):
            if f.attr == "sleep" and isinstance(f.value, ast.Name) \
                    and mod.imports.get(f.value.id) == "time":
                return mod.violation(
                    call, self.id,
                    "time.sleep() inside an async def blocks the whole "
                    "event loop (admission, coalescing, every pending "
                    "future) — use 'await asyncio.sleep(...)'")
            if f.attr in ("block_until_ready", "device_get"):
                return mod.violation(
                    call, self.id,
                    f".{f.attr}() inside an async def parks the event "
                    "loop on device completion — dispatch on the "
                    "executor thread (run_in_executor) and await the "
                    "result instead")
            if f.attr == "get" and not call.args \
                    and not any(kw.arg == "timeout"
                                for kw in call.keywords):
                return mod.violation(
                    call, self.id,
                    "argument-less .get() inside an async def reads as "
                    "a synchronous queue.get() that blocks the loop "
                    "until an item arrives — use an asyncio.Queue "
                    "('await q.get()'), or pass timeout= if this really "
                    "is a thread-queue handoff")
        return None


ALL_RULES = (
    RetraceHazardRule(),
    HostSyncRule(),
    DtypeDriftRule(),
    NondeterministicPytreeRule(),
    TelemetryInTraceRule(),
    SpillDtypeLeakRule(),
    BlockingInAsyncRule(),
)

RULE_IDS = tuple(r.id for r in ALL_RULES)
