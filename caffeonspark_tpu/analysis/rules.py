"""coslint rules COS001..COS005.

Each rule is an AST pass with an ID, a docstring stating exactly what
it catches (and what it deliberately does not), and a worked known-bad
example in tests/fixtures/coslint/.  The rules are tuned for THIS
codebase's bug history — they prefer few, high-confidence findings
over exhaustive dataflow analysis, because the tier-1 gate runs them
on every test invocation and a noisy rule would train people to
suppress reflexively.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .coslint import (Finding, ModuleCtx, dotted, own_nodes, scopes,
                      shares_loop)


class Rule:
    """Base: subclasses set `id`/`title` and implement check(ctx)."""

    id = "COS000"
    title = "abstract rule"

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleCtx, node: ast.AST,
                message: str) -> Finding:
        return Finding(self.id, ctx.rel, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


def _ordered(node: ast.AST) -> Tuple[int, int]:
    return (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))


def _is_f32_dtype(node: ast.AST) -> bool:
    d = dotted(node)
    if d.endswith("float32"):
        return True
    return (isinstance(node, ast.Constant)
            and node.value in ("float32", "f32"))


def _has_f32_cast(node: ast.AST) -> bool:
    """Does this expression subtree contain an explicit f32 upcast —
    `x.astype(jnp.float32)`, `jnp.asarray(x, jnp.float32)`,
    `jnp.array(x, dtype=np.float32)`?"""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        fn = sub.func
        if isinstance(fn, ast.Attribute) and fn.attr == "astype":
            if sub.args and _is_f32_dtype(sub.args[0]):
                return True
        name = dotted(fn)
        if name.split(".")[-1] in ("asarray", "array", "full", "zeros",
                                   "ones"):
            if any(_is_f32_dtype(a) for a in sub.args[1:]):
                return True
            if any(kw.arg == "dtype" and _is_f32_dtype(kw.value)
                   for kw in sub.keywords):
                return True
    return False


class DevicePutAliasing(Rule):
    """COS001 — host buffer staged with `jax.device_put` and mutated
    afterwards.

    On the CPU backend `device_put` ALIASES aligned host numpy buffers
    (zero-copy), and on a TPU it returns before the buffer has been
    read, so mutating the source buffer after staging corrupts the
    staged batch — the PR 3 ingest bug (see queue_runner.py's
    `_resolve_host_copy`).  Flagged: a `device_put(buf, ...)` (or
    `make_array_from_process_local_data(..., buf)`) whose buffer is a
    plain name/attribute that the same scope later mutates in place
    (`buf[...] = `, `buf += `, `buf.fill/sort/partition/resize(...)`,
    `np.copyto(buf, ...)`) — "later" includes any mutation sharing a
    loop with the put, the classic reused-pack-buffer shape.  Not
    flagged: staging a fresh copy (`np.array(x, copy=True)`,
    `x.copy()`) or rebinding the name before mutating.
    """

    id = "COS001"
    title = "device_put of a host buffer that is later mutated"

    _MUTATORS = {"fill", "sort", "partition", "resize", "itemset",
                 "setflags", "setfield", "byteswap"}

    def _put_buffer(self, call: ast.Call) -> Optional[ast.AST]:
        name = dotted(call.func)
        leaf = name.split(".")[-1]
        if leaf == "device_put" and call.args:
            return call.args[0]
        if leaf == "make_array_from_process_local_data":
            if len(call.args) >= 2:
                return call.args[1]
        return None

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        for scope in scopes(ctx):
            puts: List[Tuple[ast.Call, str]] = []
            mutations: Dict[str, List[ast.AST]] = {}
            rebinds: Dict[str, List[ast.AST]] = {}
            for node in own_nodes(scope):
                if isinstance(node, ast.Call):
                    buf = self._put_buffer(node)
                    if buf is not None:
                        target = dotted(buf)
                        if target:
                            puts.append((node, target))
                    fname = dotted(node.func)
                    if fname.split(".")[-1] == "copyto" and node.args:
                        t = dotted(node.args[0])
                        if t:
                            mutations.setdefault(t, []).append(node)
                    if (isinstance(node.func, ast.Attribute)
                            and node.func.attr in self._MUTATORS):
                        t = dotted(node.func.value)
                        if t:
                            mutations.setdefault(t, []).append(node)
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for tgt in targets:
                        if isinstance(tgt, ast.Subscript):
                            t = dotted(tgt.value)
                            if t:
                                mutations.setdefault(t, []).append(node)
                        elif isinstance(tgt, (ast.Name, ast.Attribute)):
                            t = dotted(tgt)
                            if t:
                                if isinstance(node, ast.AugAssign):
                                    mutations.setdefault(t, []).append(
                                        node)
                                else:
                                    rebinds.setdefault(t, []).append(
                                        node)
            for call, target in puts:
                for mut in mutations.get(target, ()):
                    if (_ordered(mut) > _ordered(call)
                            or shares_loop(ctx, call, mut, scope)):
                        # a rebind between put and mutation detaches
                        # the name from the staged buffer
                        if any(_ordered(call) < _ordered(rb)
                               < _ordered(mut)
                               for rb in rebinds.get(target, ())):
                            continue
                        yield self.finding(
                            ctx, call,
                            f"host buffer '{target}' is staged with "
                            "device_put and mutated afterwards — on "
                            "the CPU backend device_put aliases the "
                            "host buffer (copy first: np.array(x, "
                            "copy=True), see COS_STAGE_COPY)")
                        break


class EinsumPrecision(Rule):
    """COS002 — f32-consuming contraction without an explicit
    precision.

    On TPU, `jnp.einsum`/`dot`/`matmul` with f32 inputs default to
    bf16 MXU passes: a call site that explicitly upcasts an operand to
    float32 is *declaring* an f32-consuming path, and leaving
    `precision=`/`preferred_element_type=` unset silently throws that
    precision away — the PR 5 sp.py ring-backward bug (fixed by
    forcing HIGHEST on the p/ds-consuming einsums).  Flagged: a
    jnp/lax contraction call with no precision-related kwarg where an
    operand (inline or via a local assigned from a cast in the same
    scope) carries an explicit f32 upcast.  Not flagged: contractions
    whose operands never state f32 intent — default-precision bf16 is
    a legitimate speed choice there.
    """

    id = "COS002"
    title = "f32-consuming einsum/dot/matmul without precision="

    _CONTRACTIONS = {"einsum", "dot", "matmul", "tensordot",
                     "dot_general", "vdot", "inner"}

    def _is_contraction(self, call: ast.Call) -> bool:
        name = dotted(call.func)
        if "." not in name:
            return False
        head, leaf = name.split(".", 1)[0], name.split(".")[-1]
        return (leaf in self._CONTRACTIONS
                and head in ("jnp", "jax", "lax"))

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        for scope in scopes(ctx):
            f32_names: Set[str] = set()
            calls: List[ast.Call] = []
            for node in own_nodes(scope):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and _has_f32_cast(node.value)):
                    f32_names.add(node.targets[0].id)
                if isinstance(node, ast.Call) and \
                        self._is_contraction(node):
                    calls.append(node)
            for call in calls:
                kws = {kw.arg for kw in call.keywords}
                if kws & {"precision", "preferred_element_type"}:
                    continue
                f32 = False
                for arg in call.args:
                    if _has_f32_cast(arg):
                        f32 = True
                    elif (isinstance(arg, ast.Name)
                          and arg.id in f32_names):
                        f32 = True
                if f32:
                    leaf = dotted(call.func).split(".")[-1]
                    yield self.finding(
                        ctx, call,
                        f"{leaf} consumes an explicit float32 upcast "
                        "but sets no precision= / "
                        "preferred_element_type= — on TPU the MXU "
                        "defaults to bf16 passes and silently drops "
                        "the upcast (force HIGHEST, as in "
                        "parallel/sp.py's ring backward)")


class TraceHostReads(Rule):
    """COS003 — host-side nondeterminism inside traced code.

    A function traced by `jax.jit` / `lax.scan` / `jax.custom_vjp`
    runs ONCE at trace time: `os.environ` / `time.*` / Python or numpy
    `random` calls bake a single host value into the compiled program
    (silently stale forever after), and `.item()` / `float()` on a
    tracer either crashes or forces a sync.  Flagged, inside any
    function reachable from a trace entry in the same module:
    `os.environ[...]`/`os.getenv`, `time.*()` calls, `random.*` /
    `np.random.*` calls (jax.random is fine — it is traced), `.item()`
    calls, and `float()/int()/bool()` applied directly to a function
    parameter.  Trace entries: functions decorated with or passed (by
    name) to jit/pjit/scan/cond/while_loop/fori_loop/vmap/pmap/grad/
    value_and_grad/custom_vjp/defvjp/remat/checkpoint/pallas_call,
    plus functions RETURNED by a factory whose result is jitted
    (`jax.jit(self.train_step_fn())`).  Reachability is per-module by
    design — cross-module trace flows are covered by wiring the
    runtime RecompileGuard at the jit boundaries instead.
    """

    id = "COS003"
    title = "host nondeterminism or env read inside traced code"

    _TRACERS = {"jit", "pjit", "scan", "cond", "while_loop",
                "fori_loop", "vmap", "pmap", "grad", "value_and_grad",
                "custom_vjp", "custom_jvp", "remat", "checkpoint",
                "defvjp", "defjvp", "pallas_call", "shard_map",
                "associative_scan", "switch"}

    def _local_defs(self, ctx: ModuleCtx) -> Dict[str, List[ast.AST]]:
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
        return defs

    def _roots(self, ctx: ModuleCtx,
               defs: Dict[str, List[ast.AST]]) -> Set[ast.AST]:
        roots: Set[ast.AST] = set()

        def mark(name: str):
            for d in defs.get(name, ()):
                roots.add(d)

        def returned_defs(factory: ast.AST):
            nested = {n.name for n in ast.walk(factory)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))
                      and n is not factory}
            for node in ast.walk(factory):
                if (isinstance(node, ast.Return)
                        and isinstance(node.value, ast.Name)
                        and node.value.id in nested):
                    mark(node.value.id)

        # decorators
        for name, nodes in defs.items():
            for d in nodes:
                for dec in d.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) \
                        else dec
                    leaf = dotted(target).split(".")[-1]
                    if leaf in self._TRACERS or leaf == "partial":
                        inner = ""
                        if isinstance(dec, ast.Call) and dec.args:
                            inner = dotted(dec.args[0]).split(".")[-1]
                        if leaf != "partial" or inner in self._TRACERS:
                            roots.add(d)
        # call sites: jit(f), scan(body, ...), f.defvjp(fwd, bwd), and
        # the factory pattern jit(self.make_step()(...)) → the defs the
        # factory returns
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            leaf = dotted(node.func).split(".")[-1]
            if leaf not in self._TRACERS:
                continue
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    mark(arg.id)
                elif isinstance(arg, ast.Attribute):
                    mark(arg.attr)
                elif isinstance(arg, ast.Call):
                    factory = dotted(arg.func).split(".")[-1]
                    for d in defs.get(factory, ()):
                        returned_defs(d)
        return roots

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        defs = self._local_defs(ctx)
        roots = self._roots(ctx, defs)
        reachable = set(roots)
        frontier = list(reachable)
        while frontier:
            fn = frontier.pop()
            for node in own_nodes(fn):
                name = ""
                if isinstance(node, ast.Name):
                    name = node.id
                elif isinstance(node, ast.Attribute):
                    name = node.attr
                if not name:
                    continue
                for d in defs.get(name, ()):
                    if d not in reachable:
                        reachable.add(d)
                        frontier.append(d)
        for fn in sorted(reachable, key=_ordered):
            # float()/int() on a parameter is only a confident tracer
            # concretization for trace ROOTS (jit/scan bodies get
            # tracers as params); transitively-reachable helpers often
            # take host-side config values too
            params = ({a.arg for a in fn.args.args
                       + fn.args.posonlyargs + fn.args.kwonlyargs}
                      if fn in roots else set())
            for node in own_nodes(fn):
                yield from self._check_node(ctx, fn, node, params)

    def _check_node(self, ctx: ModuleCtx, fn: ast.AST, node: ast.AST,
                    params: Set[str]) -> Iterator[Finding]:
        where = f"'{fn.name}' is trace-reachable"
        if isinstance(node, (ast.Attribute, ast.Subscript)):
            d = dotted(node if isinstance(node, ast.Attribute)
                       else node.value)
            if d.startswith("os.environ"):
                yield self.finding(
                    ctx, node,
                    f"os.environ read inside traced code ({where}) — "
                    "the value is baked into the compiled program at "
                    "trace time; hoist it to construction/plan time")
                return
        if not isinstance(node, ast.Call):
            return
        d = dotted(node.func)
        leaf = d.split(".")[-1]
        if d == "os.getenv":
            yield self.finding(
                ctx, node,
                f"os.getenv inside traced code ({where}) — hoist the "
                "env read out of the traced function")
        elif d.startswith("time."):
            yield self.finding(
                ctx, node,
                f"host clock call {d}() inside traced code ({where}) "
                "— trace-time timestamps are frozen into the program")
        elif (d.startswith("random.")
              or d.startswith("np.random.")
              or d.startswith("numpy.random.")):
            yield self.finding(
                ctx, node,
                f"host RNG call {d}() inside traced code ({where}) — "
                "draws once at trace time; use jax.random with a "
                "threaded key")
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "item" and not node.args):
            yield self.finding(
                ctx, node,
                f".item() inside traced code ({where}) — forces a "
                "host sync / fails on tracers; keep values on device")
        elif (leaf in ("float", "int", "bool") and "." not in d
              and len(node.args) == 1
              and isinstance(node.args[0], ast.Name)
              and node.args[0].id in params):
            yield self.finding(
                ctx, node,
                f"{leaf}() on traced argument "
                f"'{node.args[0].id}' ({where}) — concretizes a "
                "tracer; use jnp casts instead")


class DonationUseAfter(Rule):
    """COS004 — buffer used after being passed to a donating call.

    `jax.jit(..., donate_argnums=...)` hands the argument's buffer to
    XLA: after the call the array is deleted (TPU) or silently aliased
    (backends that ignore donation) — reading it is either a crash or
    a heisenbug.  Flagged: within one scope, a name assigned from
    `jax.jit(..., donate_argnums=...)` is called, and a donated
    positional arg (a plain name) is read again afterwards without
    being rebound.  The runtime counterpart is the COS_DONATION_POISON
    wrapper (analysis/runtime.py), which deletes donated buffers after
    every call so cross-module violations fail loudly in debug runs.
    """

    id = "COS004"
    title = "use of a buffer after donation"

    def _donating_assigns(self, scope) -> Dict[str, Tuple[int, ...]]:
        out: Dict[str, Tuple[int, ...]] = {}
        for node in own_nodes(scope):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            if dotted(call.func).split(".")[-1] not in ("jit", "pjit"):
                continue
            for kw in call.keywords:
                if kw.arg != "donate_argnums":
                    continue
                nums: List[int] = []
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, int):
                        nums.append(el.value)
                if nums:
                    out[node.targets[0].id] = tuple(nums)
        return out

    def _stmt_pos(self, ctx: ModuleCtx, node: ast.AST) -> Tuple[int, int]:
        """Position of the enclosing STATEMENT — all of a statement's
        argument reads happen before its call executes and before its
        assignment targets bind, so ordering is (statement position,
        read < donate < rebind)."""
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = ctx.parents.get(cur)
        return _ordered(cur if cur is not None else node)

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        for scope in scopes(ctx):
            donating = self._donating_assigns(scope)
            if not donating:
                continue
            ranks = {"read": 0, "donate": 1, "rebind": 2}
            events: List[Tuple[Tuple[int, int], int, str, ast.AST]] = []
            loops_of: Dict[str, List[ast.AST]] = {}
            for node in own_nodes(scope):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in donating):
                    for pos in donating[node.func.id]:
                        if pos < len(node.args) and \
                                isinstance(node.args[pos], ast.Name):
                            events.append(
                                (self._stmt_pos(ctx, node),
                                 ranks["donate"], "donate",
                                 node.args[pos]))
                elif isinstance(node, ast.Name):
                    kind = ("rebind"
                            if isinstance(node.ctx, ast.Store)
                            else "read")
                    events.append((self._stmt_pos(ctx, node),
                                   ranks[kind], kind, node))
                    if kind == "rebind":
                        loops_of.setdefault(node.id, []).append(node)
            events.sort(key=lambda e: (e[0], e[1]))
            live: Dict[str, ast.AST] = {}
            flagged: Set[str] = set()
            for _, _, kind, node in events:
                name = node.id
                if kind == "donate":
                    live.setdefault(name, node)
                    # donating inside a loop without rebinding the name
                    # in that loop: iteration 2 reads a donated buffer
                    if name not in flagged and not any(
                            shares_loop(ctx, node, rb, scope)
                            for rb in loops_of.get(name, ())):
                        if ctx.enclosing(node, (ast.For, ast.While,
                                                ast.AsyncFor)):
                            flagged.add(name)
                            yield self.finding(
                                ctx, node,
                                f"'{name}' is donated inside a loop "
                                "but never rebound there — the next "
                                "iteration reads a deleted/aliased "
                                "buffer; rebind it from the call's "
                                "result")
                elif kind == "rebind":
                    live.pop(name, None)
                elif kind == "read" and name in live and \
                        name not in flagged:
                    flagged.add(name)
                    yield self.finding(
                        ctx, node,
                        f"'{name}' is read after being donated to a "
                        "jit(donate_argnums=...) call — the buffer "
                        "is deleted or aliased by XLA; rebind the "
                        "name from the call's result (or drop the "
                        "donation)")


class LockAcrossBlocking(Rule):
    """COS005 — lock held across a blocking call, and lock-order
    inversions.

    The threaded runtime (serving/batcher.py, the ingest
    TransformerPool, mini_cluster.py, spark_daemon.py) follows one
    discipline: a lock protects STATE TRANSITIONS, never waits.  A
    blocking call under a lock (queue get/put, FeedQueue take/offer,
    Event.wait, socket I/O, thread join, sleep) turns backpressure
    into deadlock the moment the unblocker needs the same lock.
    Flagged: inside a `with <lock>` body — where <lock> is named
    *lock*/*cond*/*mutex* or assigned from threading.Lock/RLock/
    Condition/Semaphore — calls to `.get`/`.put` on queue-like
    receivers (or with timeout=/block=), `.take`/`.offer`, `.wait` on
    anything OTHER than the held lock (Condition.wait on the held
    condition releases it and is fine), `.join` on thread-like
    receivers, `time.sleep`, and socket send/recv/accept/connect.
    Also flagged: two functions acquiring the same pair of locks in
    opposite nesting orders (the cross-function deadlock witness; the
    runtime LockWitness catches the dynamic version in stress tests).
    """

    id = "COS005"
    title = "lock held across a blocking call / lock-order inversion"

    _LOCK_NAME = ("lock", "mutex", "cond", "condition", "sem")
    _LOCK_CTORS = ("Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore")
    _QUEUE_NAME = ("q", "queue", "work", "outq", "inq", "feed",
                   "results")
    _THREAD_NAME = ("thread", "proc", "process", "worker", "stager",
                    "dispatcher", "reader", "snapshotter")
    _SOCKET_OPS = ("recv", "recvfrom", "send", "sendall", "accept",
                   "connect")

    def _lock_attrs(self, ctx: ModuleCtx) -> Set[str]:
        """Names assigned from threading lock constructors —
        class-qualified for self.* attributes so two classes' _lock
        fields stay distinct."""
        out: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            leaf = dotted(node.value.func).split(".")[-1]
            if leaf not in self._LOCK_CTORS:
                continue
            for tgt in node.targets:
                d = dotted(tgt)
                if d:
                    out.add(self._qualify(ctx, node, d))
        return out

    def _qualify(self, ctx: ModuleCtx, node: ast.AST, d: str) -> str:
        if d.startswith("self."):
            cls = ctx.enclosing_class_name(node)
            return f"{cls}.{d[5:]}" if cls else d
        return d

    def _looks_like_lock(self, ctx: ModuleCtx, node: ast.AST,
                         expr: ast.AST, known: Set[str]) -> str:
        d = dotted(expr)
        if not d:
            return ""
        q = self._qualify(ctx, node, d)
        leaf = d.split(".")[-1].lower()
        if q in known or any(k in leaf for k in self._LOCK_NAME):
            return q
        return ""

    def _name_matches(self, receiver: str,
                      pats: Sequence[str]) -> bool:
        leaf = receiver.split(".")[-1].lower().strip("_")
        return any(p == leaf or p in leaf for p in pats)

    def _blocking(self, call: ast.Call, held: List[str],
                  ctx: ModuleCtx) -> str:
        """Return a description if this call can block, else ''."""
        fn = call.func
        d = dotted(fn)
        if d == "time.sleep":
            return "time.sleep()"
        if not isinstance(fn, ast.Attribute):
            return ""
        recv = dotted(fn.value)
        attr = fn.attr
        kwargs = {kw.arg for kw in call.keywords}
        if attr in ("get", "put"):
            if self._name_matches(recv, self._QUEUE_NAME) \
                    or kwargs & {"timeout", "block"}:
                return f"{recv}.{attr}()"
        if attr in ("take", "offer"):
            return f"{recv}.{attr}()"
        if attr == "wait":
            q = self._qualify(ctx, call, recv)
            if q not in held:
                return f"{recv}.wait()"
        if attr == "join" and self._name_matches(recv,
                                                 self._THREAD_NAME):
            return f"{recv}.join()"
        if attr in self._SOCKET_OPS:
            return f"{recv}.{attr}()"
        nonblocking = (
            (call.args and isinstance(call.args[0], ast.Constant)
             and call.args[0].value is False)
            or any(kw.arg == "blocking"
                   and isinstance(kw.value, ast.Constant)
                   and kw.value.value is False
                   for kw in call.keywords))
        if attr == "acquire" and not nonblocking:
            q = self._qualify(ctx, call, recv)
            if q not in held and self._looks_like_lock(
                    ctx, call, fn.value, set()):
                return f"{recv}.acquire()"
        return ""

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        known = self._lock_attrs(ctx)
        # edges[(outer, inner)] = (scope_name, with_node)
        edges: Dict[Tuple[str, str], Tuple[str, ast.AST]] = {}
        for scope in scopes(ctx):
            sname = getattr(scope, "name", "<module>")
            yield from self._walk_body(
                ctx, scope, list(ast.iter_child_nodes(scope)), [],
                known, edges, sname)
        for (a, b), (fn_a, node_a) in sorted(
                edges.items(), key=lambda kv: _ordered(kv[1][1])):
            if (b, a) in edges and a < b:
                fn_b, node_b = edges[(b, a)]
                yield self.finding(
                    ctx, node_a,
                    f"lock-order inversion: '{fn_a}' acquires "
                    f"{a} then {b}, but '{fn_b}' (line "
                    f"{node_b.lineno}) acquires {b} then {a} — "
                    "pick one order (deadlock witness)")

    def _walk_body(self, ctx: ModuleCtx, scope, nodes: List[ast.AST],
                   held: List[str], known: Set[str],
                   edges: Dict, sname: str) -> Iterator[Finding]:
        for node in nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            if isinstance(node, ast.With):
                acquired: List[str] = []
                for item in node.items:
                    lk = self._looks_like_lock(
                        ctx, node, item.context_expr, known)
                    if lk:
                        for h in held + acquired:
                            if h != lk:
                                edges.setdefault((h, lk),
                                                 (sname, node))
                        acquired.append(lk)
                yield from self._walk_body(
                    ctx, scope, node.body, held + acquired, known,
                    edges, sname)
                continue
            if held and isinstance(node, ast.Call):
                why = self._blocking(node, held, ctx)
                if why:
                    yield self.finding(
                        ctx, node,
                        f"blocking call {why} while holding "
                        f"{held[-1]} — waits must happen outside "
                        "the lock (or via Condition.wait on the "
                        "held condition)")
            yield from self._walk_body(
                ctx, scope, list(ast.iter_child_nodes(node)), held,
                known, edges, sname)


ALL_RULES = (DevicePutAliasing, EinsumPrecision, TraceHostReads,
             DonationUseAfter, LockAcrossBlocking)
