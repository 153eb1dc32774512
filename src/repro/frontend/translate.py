"""AST -> ``KernelDef`` translator: the heart of the CUDA-C frontend.

The translator emits *Python source* for each barrier-separated stage and
``exec``s it against a tiny namespace (``jnp``, the carry helper and
CUDA-accurate ``expf``/``logf``), so a
translated kernel is structurally indistinguishable from a hand-written
one: same ``(ctx, st) -> st`` stage signature, same thread-chunk
polymorphism, same fingerprint-hash behavior (all constants are inlined
as literals, which land in ``co_consts`` and hash stably; exec'd
functions close over nothing).

Bit-faithfulness is the design constraint that shapes every emission
rule.  Conditional stores lower to the suite's sentinel idiom
(``arr.at[jnp.where(mask, idx, 1 << 30)].set(v, mode="drop")``),
``min``/``max`` map to ``jnp.minimum``/``jnp.maximum``, C's
left-associative float arithmetic is preserved parenthesis-for-
parenthesis, and atomics call the exact :class:`~repro.core.kernel.Ctx`
entry points the hand-written suite uses - so an ingested ``.cu`` kernel
produces bit-identical buffers to its hand-written twin (enforced by the
``mode="frontend"`` conformance cells).

Divergence is handled with masks, not control flow: an ``if`` body
executes for all threads with its stores masked - the SPMD semantics
every lowering expects.  Barriers must sit in uniform (top-level)
control flow; a ``__syncthreads()`` inside an ``if`` or ``for`` is
diagnosed, not mistranslated.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.kernel import KernelDef, UnsupportedKernel, expf, logf
from repro.frontend import parser as P
from repro.frontend.lexer import macro_names
from repro.frontend.runtime import carry

#: out-of-bounds sentinel for masked stores; matches cuda_suite.OOB
OOB = 1 << 30

_DTYPE = {"int": jnp.int32, "float": jnp.float32, "double": jnp.float64,
          "unsigned": jnp.uint32, "uint32_t": jnp.uint32,
          "int32_t": jnp.int32, "bool": jnp.bool_, "char": jnp.int8}

_TYPE_CLASS = {"float": "float", "double": "float"}   # everything else int

#: C math intrinsics -> jnp, with the result type class
_MATH = {
    "min": ("jnp.minimum", None), "max": ("jnp.maximum", None),
    "fminf": ("jnp.minimum", "float"), "fmaxf": ("jnp.maximum", "float"),
    "fmin": ("jnp.minimum", "float"), "fmax": ("jnp.maximum", "float"),
    "abs": ("jnp.abs", None), "fabs": ("jnp.abs", "float"),
    "fabsf": ("jnp.abs", "float"),
    "expf": ("expf", "float"), "exp": ("expf", "float"),
    "logf": ("logf", "float"), "log": ("logf", "float"),
    "sqrtf": ("jnp.sqrt", "float"), "sqrt": ("jnp.sqrt", "float"),
    "powf": ("jnp.power", "float"), "pow": ("jnp.power", "float"),
}

_SHFL = {"__shfl_sync": "ctx.shfl", "__shfl_up_sync": "ctx.shfl_up",
         "__shfl_down_sync": "ctx.shfl_down",
         "__shfl_xor_sync": "ctx.shfl_xor"}

_VOTE = {"__ballot_sync": "ctx.ballot", "__all_sync": "ctx.vote_all",
         "__any_sync": "ctx.vote_any"}

_ATOMICS = ("atomicAdd", "atomicMax", "atomicMin", "atomicCAS",
            "atomicExch")

_RESERVED = {"ctx", "st", "jnp", "_carry", "range", "expf", "logf"}


@dataclasses.dataclass(frozen=True)
class TranslatedKernel:
    """A ``.cu`` kernel after translation.

    ``kernel`` is the ready-to-launch :class:`KernelDef`; ``sources``
    holds the generated Python per stage (also attached to each stage
    function as ``__cuda_source__`` for debugging); ``constants`` names
    the file-scope ``__constant__`` buffers the kernel expects in the
    heap (bind them via ``SuiteEntry.const`` / ``ConstArray``).
    """

    kernel: KernelDef
    sources: tuple[str, ...]
    cu_name: str
    params: tuple[str, ...]
    constants: tuple[str, ...]


def _err(line: int, msg: str) -> UnsupportedKernel:
    return UnsupportedKernel(f"line {line}: {msg}")


def _fold(e) -> int | float:
    """Constant-fold an expression (shared shapes, loop bounds)."""
    if isinstance(e, P.Num):
        return e.value
    if isinstance(e, P.Unary) and e.op == "-":
        return -_fold(e.operand)
    if isinstance(e, P.Bin):
        lhs, rhs = _fold(e.lhs), _fold(e.rhs)
        ops = {"+": lambda a, b: a + b, "-": lambda a, b: a - b,
               "*": lambda a, b: a * b, "%": lambda a, b: a % b,
               "<<": lambda a, b: a << b, ">>": lambda a, b: a >> b,
               "/": lambda a, b: a // b if isinstance(a, int)
               and isinstance(b, int) else a / b}
        if e.op in ops:
            return ops[e.op](lhs, rhs)
    line = getattr(e, "line", 0)
    raise _err(line, "expression must be a compile-time constant here "
                     "(array sizes and for-loop bounds)")


def _unify(a: str, b: str) -> str:
    if "float" in (a, b):
        return "float"
    if a == "bool" and b == "bool":
        return "bool"
    return "int"


class _Translator:
    def __init__(self, kernel: P.KernelAST,
                 constants: tuple[P.ConstantDecl, ...],
                 scalar_bind: dict):
        self.k = kernel
        # buffer name -> element type class
        self.globals: dict[str, str] = {}
        self.const_names: list[str] = []
        self.param_order: list[str] = []
        for c in constants:
            _fold(c.size)                      # must be constant; validates
            self.globals[c.name] = _TYPE_CLASS.get(c.ctype, "int")
            self.const_names.append(c.name)
        self.scalar_bind = dict(scalar_bind)
        for p in kernel.params:
            self._check_name(p.name, p.line)
            if p.is_pointer:
                self.globals[p.name] = _TYPE_CLASS.get(p.ctype, "int")
                self.param_order.append(p.name)
            elif p.name not in self.scalar_bind:
                raise _err(
                    p.line,
                    f"scalar parameter {p.name!r} has no launch value: "
                    f"pass bind={{{p.name!r}: <value>}} to translate() "
                    f"(scalar kernel arguments are specialized at "
                    f"translation time, the POCL-style JIT idiom)")
        self.shared_spec: dict[str, tuple] = {}
        self.shared_type: dict[str, str] = {}
        for sd in kernel.shareds:
            self._check_name(sd.name, sd.line)
            if sd.name in self.globals:
                raise _err(sd.line, f"__shared__ {sd.name!r} shadows a "
                                    f"kernel parameter")
            dt = _DTYPE.get(sd.ctype)
            if dt is None:
                raise _err(sd.line, f"unsupported __shared__ element type "
                                    f"{sd.ctype!r}")
            shape = ((-1,) if sd.dynamic
                     else (int(_fold(sd.shape[0])),))
            self.shared_spec[sd.name] = (shape, dt)
            self.shared_type[sd.name] = _TYPE_CLASS.get(sd.ctype, "int")

        self.locals: dict[str, str] = {}       # name -> type class
        self.written: set[str] = set()         # global buffers stored to
        self.uses_warp = False
        self.tmp = 0
        # per-stage emission state
        self.lines: list[str] = []
        self.indent = 1
        self.mask: str | None = None

    def _check_name(self, name: str, line: int):
        if name in _RESERVED or name.startswith("_"):
            raise _err(line, f"identifier {name!r} collides with the "
                             f"translation runtime (reserved names: "
                             f"{sorted(_RESERVED)}, leading underscores)")

    # ------------------------------------------------------------------
    def run(self) -> tuple[list[str], dict]:
        stages = self._split_stages()
        scans = [self._scan(s) for s in stages]
        local_defs: dict[str, int] = {}
        for i, (refs, defs, _members) in enumerate(scans):
            for d in defs:
                local_defs.setdefault(d, i)

        def carry_set(barrier: int) -> list[str]:
            out = set()
            for v, ds in local_defs.items():
                if ds <= barrier and any(
                        v in scans[j][0] for j in
                        range(barrier + 1, len(stages))):
                    out.add(v)
            return sorted(out)

        any_carry = any(carry_set(i) for i in range(len(stages) - 1))
        sources = []
        for i, body in enumerate(stages):
            refs, _defs, members = scans[i]
            carried_in = carry_set(i - 1) if i > 0 else []
            carried_out = carry_set(i) if i < len(stages) - 1 else []
            src = self._emit_stage(i, body, refs, members, carried_in,
                                   carried_out,
                                   final=(i == len(stages) - 1),
                                   any_carry=any_carry)
            sources.append(src)
        writes = tuple(n for n in self.param_order if n in self.written)
        if not writes:
            raise UnsupportedKernel(
                f"kernel {self.k.name}: no global buffer is ever written "
                f"(a kernel with no observable effect is out of subset)")
        reads = tuple(self.param_order) + tuple(self.const_names)
        meta = {"writes": writes, "reads": reads,
                "shared": dict(self.shared_spec),
                "uses_warp": self.uses_warp}
        return sources, meta

    def _split_stages(self) -> list[list]:
        stages, cur = [], []
        for stmt in self.k.body:
            if isinstance(stmt, P.Barrier):
                stages.append(cur)
                cur = []
            else:
                cur.append(stmt)
        stages.append(cur)
        return stages

    # ------------------------------------------------------------------
    def _scan(self, stmts) -> tuple[set, set, set]:
        """(referenced identifiers, declared locals, special members)."""
        refs: set[str] = set()
        defs: set[str] = set()
        members: set[str] = set()

        def expr(e):
            if isinstance(e, P.Name):
                refs.add(e.id)
            elif isinstance(e, P.Member):
                members.add(e.base)
            elif isinstance(e, P.Index):
                refs.add(e.base)
                expr(e.index)
            elif isinstance(e, P.Unary):
                expr(e.operand)
            elif isinstance(e, P.Bin):
                expr(e.lhs)
                expr(e.rhs)
            elif isinstance(e, P.CondExpr):
                expr(e.cond)
                expr(e.then)
                expr(e.els)
            elif isinstance(e, P.Call):
                for a in e.args:
                    expr(a)
            elif isinstance(e, P.AddrOf):
                expr(e.target)

        def stmt(s):
            if isinstance(s, P.Decl):
                defs.add(s.name)
                if s.init is not None:
                    expr(s.init)
            elif isinstance(s, P.Assign):
                expr(s.target)
                expr(s.value)
            elif isinstance(s, P.If):
                expr(s.cond)
                for x in s.then:
                    stmt(x)
                for x in s.els:
                    stmt(x)
            elif isinstance(s, P.For):
                defs.add(s.var)
                for x in (s.start, s.bound, s.step):
                    expr(x)
                for x in s.body:
                    stmt(x)
            elif isinstance(s, P.ExprStmt):
                expr(s.expr)

        for s in stmts:
            stmt(s)
        return refs, defs, members

    # ------------------------------------------------------------------
    def _emit_stage(self, i: int, body, refs, members, carried_in,
                    carried_out, final: bool, any_carry: bool) -> str:
        self.lines = [f"def stage_{i}(ctx, st):"]
        self.indent = 1
        self.mask = None
        self.final_stage = final
        self.stage_written: set[str] = set()
        self.stage_shared_written: set[str] = set()
        if "threadIdx" in members:
            self.emit("_tidx, _tidy, _tidz = ctx.tid3")
        if "blockIdx" in members:
            self.emit("_bidx, _bidy, _bidz = ctx.bid3")
        for name in self.param_order + self.const_names:
            if name in refs:
                self.emit(f'{name} = st.glob["{name}"]')
        for name in self.shared_spec:
            if name in refs:
                self.emit(f'{name} = st.shared["{name}"]')
        for name in carried_in:
            self.emit(f'{name} = st.priv["{name}"]')
        self._stmts(body)
        sw = [n for n in self.shared_spec if n in self.stage_shared_written]
        if sw:
            self.emit("st = st.set_shared("
                      + ", ".join(f"{n}={n}" for n in sw) + ")")
        gw = [n for n in self.param_order if n in self.stage_written]
        if gw:
            self.emit("st = st.set_glob("
                      + ", ".join(f"{n}={n}" for n in gw) + ")")
        if carried_out:
            kv = ", ".join(f'"{n}": _carry({n}, ctx.tid)'
                           for n in carried_out)
            self.emit("st = st.with_priv({" + kv + "})")
        elif any_carry and (final or i > 0):
            self.emit("st = st.with_priv({})")
        self.emit("return st")
        return "\n".join(self.lines) + "\n"

    def emit(self, line: str):
        self.lines.append("    " * self.indent + line)

    def _tmpname(self, prefix: str) -> str:
        self.tmp += 1
        return f"_{prefix}{self.tmp}"

    # ---- statements ---------------------------------------------------
    def _stmts(self, stmts):
        outer_mask = self.mask
        it = iter(enumerate(stmts))
        for pos, s in it:
            if isinstance(s, P.Barrier):
                raise _err(s.line,
                           "__syncthreads() inside an if/for body: "
                           "barriers must sit in uniform top-level "
                           "control flow (the fission points)")
            if isinstance(s, P.Return):
                if not self.final_stage:
                    raise _err(s.line, "'return' before a later "
                                       "__syncthreads(): returning past a "
                                       "barrier other threads reach is "
                                       "undefined in CUDA")
                if self.mask is not None:
                    raise _err(s.line, "'return' under divergent control "
                                       "flow must be the lone statement "
                                       "of its if-body")
                break                          # dead code after return
            if (isinstance(s, P.If) and len(s.then) == 1 and not s.els
                    and isinstance(s.then[0], P.Return)):
                if not self.final_stage:
                    raise _err(s.then[0].line,
                               "'return' before a later __syncthreads(): "
                               "returning past a barrier other threads "
                               "reach is undefined in CUDA")
                self._early_return(s, stmts[pos + 1:])
                self.mask = outer_mask
                return
            self._stmt(s)
        self.mask = outer_mask

    def _early_return(self, s: P.If, rest):
        cond, ct = self._expr(s.cond)
        cv = self._tmpname("c")
        self.emit(f"{cv} = {self._bool(cond, ct)}")
        keep = (f"({self.mask} & (~{cv}))" if self.mask is not None
                else f"(~{cv})")
        mv = self._tmpname("m")
        self.emit(f"{mv} = {keep}")
        self.mask = mv
        self._stmts(rest)

    def _stmt(self, s):
        if isinstance(s, P.Decl):
            self._check_name(s.name, s.line)
            if s.name in self.globals or s.name in self.shared_spec:
                raise _err(s.line, f"local {s.name!r} shadows a buffer")
            if s.init is None:
                raise _err(s.line, f"local {s.name!r} must be "
                                   f"initialized at declaration")
            if self._is_atomic_call(s.init):
                self._atomic(s.init, capture=s.name)
                return
            code, t = self._expr(s.init)
            self.emit(f"{s.name} = {code}")
            self.locals[s.name] = t
        elif isinstance(s, P.Assign):
            self._assign(s)
        elif isinstance(s, P.If):
            self._if(s)
        elif isinstance(s, P.For):
            self._for(s)
        elif isinstance(s, P.ExprStmt):
            if self._is_atomic_call(s.expr):
                self._atomic(s.expr, capture=None)
            else:
                raise _err(s.line, "expression statement has no effect "
                                   "(only atomic calls may stand alone)")
        else:                                   # pragma: no cover
            raise _err(getattr(s, "line", 0),
                       f"unsupported statement {type(s).__name__}")

    def _assign(self, s: P.Assign):
        if isinstance(s.target, P.Name):
            name = s.target.id
            if name in self.globals or name in self.shared_spec:
                raise _err(s.line, f"cannot assign a whole buffer "
                                   f"({name!r}); store to an element")
            if self._is_atomic_call(s.value) and s.op == "=":
                self._atomic(s.value, capture=name)
                return
            value = s.value
            if s.op != "=":
                value = P.Bin(s.op[:-1], s.target, s.value, s.line)
            code, t = self._expr(value)
            if self.mask is not None:
                if name not in self.locals:
                    raise _err(s.line,
                               f"{name!r} assigned under an if but never "
                               f"declared before it (masked assignment "
                               f"needs a prior value)")
                self.emit(f"{name} = jnp.where({self.mask}, {code}, "
                          f"{name})")
                self.locals[name] = _unify(self.locals[name], t)
            else:
                self.emit(f"{name} = {code}")
                self.locals[name] = t
            return
        # buffer element store
        buf, idx_e = s.target.base, s.target.index
        if buf in self.locals:
            raise _err(s.line, f"cannot subscript local {buf!r}")
        if buf in self.const_names:
            raise _err(s.line, f"store to __constant__ buffer {buf!r}")
        is_shared = buf in self.shared_spec
        if not is_shared and buf not in self.globals:
            raise _err(s.line, f"store to unknown buffer {buf!r}")
        idx, _ = self._expr(idx_e)
        if s.op == "=":
            val, _ = self._expr(s.value)
            op, args = "set", val
        elif s.op in ("+=", "-="):
            val, _ = self._expr(s.value)
            args = val if s.op == "+=" else f"(-{val})"
            op = "add"
        else:
            raise _err(s.line, f"{s.op!r} on a buffer element is out of "
                               f"subset (use = / += / -=)")
        if self.mask is not None:
            self.emit(f"{buf} = {buf}.at[jnp.where({self.mask}, {idx}, "
                      f"{OOB})].{op}({args}, mode=\"drop\")")
        else:
            self.emit(f"{buf} = {buf}.at[{idx}].{op}({args})")
        if is_shared:
            self.stage_shared_written.add(buf)
        else:
            self.written.add(buf)
            self.stage_written.add(buf)

    def _if(self, s: P.If):
        cond, ct = self._expr(s.cond)
        cv = self._tmpname("c")
        self.emit(f"{cv} = {self._bool(cond, ct)}")
        outer = self.mask
        then_mask = cv if outer is None else f"({outer} & {cv})"
        mv = self._tmpname("m")
        self.emit(f"{mv} = {then_mask}")
        self.mask = mv
        self._stmts(s.then)
        if s.els:
            els_mask = (f"(~{cv})" if outer is None
                        else f"({outer} & (~{cv}))")
            ev = self._tmpname("m")
            self.emit(f"{ev} = {els_mask}")
            self.mask = ev
            self._stmts(s.els)
        self.mask = outer

    def _for(self, s: P.For):
        self._check_name(s.var, s.line)
        start, bound, step = _fold(s.start), _fold(s.bound), _fold(s.step)
        if not all(isinstance(v, int) for v in (start, bound, step)):
            raise _err(s.line, "for-loop bounds must be integer constants")
        if step <= 0:
            raise _err(s.line, "for-loop step must be positive")
        stop = bound + 1 if s.cond_op == "<=" else bound
        self.emit(f"for {s.var} in range({start}, {stop}, {step}):")
        self.locals[s.var] = "int"
        self.indent += 1
        self._stmts(s.body)
        self.indent -= 1

    # ---- atomics ------------------------------------------------------
    def _is_atomic_call(self, e) -> bool:
        return isinstance(e, P.Call) and e.fn in _ATOMICS

    def _atomic(self, call: P.Call, capture: str | None):
        fn, line = call.fn, call.line
        nargs = {"atomicAdd": 2, "atomicMax": 2, "atomicMin": 2,
                 "atomicExch": 2, "atomicCAS": 3}[fn]
        if len(call.args) != nargs:
            raise _err(line, f"{fn} takes {nargs} arguments")
        target = call.args[0]
        if not isinstance(target, P.AddrOf):
            raise _err(line, f"{fn}'s first argument must be "
                             f"&buffer[index]")
        buf, idx_e = target.target.base, target.target.index
        if buf in self.shared_spec:
            raise _err(line, f"{fn} on __shared__ memory is out of "
                             f"subset (global buffers only)")
        if buf in self.const_names:
            raise _err(line, f"{fn} on __constant__ buffer {buf!r}")
        if buf not in self.globals:
            raise _err(line, f"{fn} on unknown buffer {buf!r}")
        idx, _ = self._expr(idx_e)
        # a scalar index (e.g. &buf[0]) must fan out to the thread axis:
        # ctx atomics serialize per-thread and index idx[t]
        idx = f"jnp.broadcast_to(jnp.asarray({idx}), ctx.tid.shape)"
        elem_t = self.globals[buf]
        if fn in ("atomicAdd", "atomicMax", "atomicMin"):
            if capture is not None:
                raise _err(line, f"capturing the old value of {fn} is "
                                 f"out of subset (only atomicCAS and "
                                 f"atomicExch return it here)")
            if self.mask is not None:
                idx = f"jnp.where({self.mask}, {idx}, {OOB})"
            val, _ = self._expr(call.args[1])
            meth = {"atomicAdd": "atomic_add", "atomicMax": "atomic_max",
                    "atomicMin": "atomic_min"}[fn]
            self.emit(f"{buf} = ctx.{meth}({buf}, {idx}, {val})")
        else:
            # cas/exch never match/always store: mask by sending inactive
            # threads to index == len(buf), which _serial_rmw treats as
            # inactive (the negative/past-the-end contract)
            if self.mask is not None:
                idx = f"jnp.where({self.mask}, {idx}, {buf}.shape[0])"
            old = self._tmpname("old")
            if fn == "atomicCAS":
                cmp_c, _ = self._expr(call.args[1])
                val, _ = self._expr(call.args[2])
                self.emit(f"{buf}, {old} = ctx.atomic_cas({buf}, {idx}, "
                          f"{cmp_c}, {val})")
            else:
                val, _ = self._expr(call.args[1])
                self.emit(f"{buf}, {old} = ctx.atomic_exch({buf}, {idx}, "
                          f"{val})")
            if capture is not None:
                self._check_name(capture, line)
                self.emit(f"{capture} = {old}")
                self.locals[capture] = elem_t
        self.written.add(buf)
        self.stage_written.add(buf)

    # ---- expressions --------------------------------------------------
    def _bool(self, code: str, t: str) -> str:
        return code if t == "bool" else f"({code} != 0)"

    def _expr(self, e) -> tuple[str, str]:
        if isinstance(e, P.Num):
            return repr(e.value), \
                "float" if isinstance(e.value, float) else "int"
        if isinstance(e, P.Name):
            if e.id in self.locals:
                return e.id, self.locals[e.id]
            if e.id in self.scalar_bind:
                v = self.scalar_bind[e.id]
                return repr(v), \
                    "float" if isinstance(v, float) else "int"
            if e.id in self.globals or e.id in self.shared_spec:
                raise _err(e.line, f"buffer {e.id!r} used as a scalar "
                                   f"value (subscript it)")
            raise _err(e.line, f"unknown identifier {e.id!r}")
        if isinstance(e, P.Member):
            if e.base == "threadIdx":
                return f"_tid{e.field}", "int"
            if e.base == "blockIdx":
                return f"_bid{e.field}", "int"
            if e.base == "blockDim":
                return f"ctx.block_dim3.{e.field}", "int"
            return f"ctx.grid_dim3.{e.field}", "int"
        if isinstance(e, P.Index):
            base = e.base
            if base in self.locals:
                raise _err(e.line, f"cannot subscript local {base!r}")
            if base not in self.globals and base not in self.shared_spec:
                raise _err(e.line, f"unknown buffer {base!r}")
            idx, _ = self._expr(e.index)
            t = (self.shared_type[base] if base in self.shared_spec
                 else self.globals[base])
            return f"{base}[{idx}]", t
        if isinstance(e, P.Unary):
            code, t = self._expr(e.operand)
            if e.op == "-":
                return f"(-{code})", t
            if e.op == "!":
                return f"jnp.logical_not({self._bool(code, t)})", "bool"
            return f"(~{code})", "int"          # '~'
        if isinstance(e, P.Bin):
            return self._bin(e)
        if isinstance(e, P.CondExpr):
            c, ct = self._expr(e.cond)
            a, at = self._expr(e.then)
            b, bt = self._expr(e.els)
            return (f"jnp.where({self._bool(c, ct)}, {a}, {b})",
                    _unify(at, bt))
        if isinstance(e, P.Call):
            return self._call(e)
        if isinstance(e, P.AddrOf):
            raise _err(e.line, "'&buffer[i]' is only valid as an atomic "
                               "target")
        raise _err(getattr(e, "line", 0),        # pragma: no cover
                   f"unsupported expression {type(e).__name__}")

    def _bin(self, e: P.Bin) -> tuple[str, str]:
        lc, lt = self._expr(e.lhs)
        rc, rt = self._expr(e.rhs)
        op = e.op
        if op in ("&&", "||"):
            py = "&" if op == "&&" else "|"
            return (f"({self._bool(lc, lt)} {py} {self._bool(rc, rt)})",
                    "bool")
        if op in ("==", "!=", "<", "<=", ">", ">="):
            return f"({lc} {op} {rc})", "bool"
        if op == "/":
            if lt != "float" and rt != "float":
                # C truncates toward zero; // floors.  Equal for the
                # non-negative operands the subset's kernels use -
                # documented limitation (docs/frontend.md)
                return f"({lc} // {rc})", "int"
            return f"({lc} / {rc})", "float"
        if op in ("&", "|", "^"):
            t = "bool" if lt == "bool" and rt == "bool" else "int"
            return f"({lc} {op} {rc})", t
        if op in ("<<", ">>", "%"):
            return f"({lc} {op} {rc})", "int"
        return f"({lc} {op} {rc})", _unify(lt, rt)   # + - *

    def _call(self, e: P.Call) -> tuple[str, str]:
        fn = e.fn
        if fn in _MATH:
            jfn, rt = _MATH[fn]
            parts = [self._expr(a) for a in e.args]
            t = rt
            if t is None:
                t = "int"
                for _, at in parts:
                    t = _unify(t, at)
            return (f"{jfn}({', '.join(c for c, _ in parts)})", t)
        if fn == "__syncthreads_count":
            if len(e.args) != 1:
                raise _err(e.line, "__syncthreads_count takes 1 argument")
            if self.mask is not None:
                raise _err(e.line, "__syncthreads_count inside divergent "
                                   "control flow")
            self.uses_warp = True
            c, t = self._expr(e.args[0])
            return f"ctx.syncthreads_count({self._bool(c, t)})", "int"
        if fn in _SHFL:
            if len(e.args) != 3:
                raise _err(e.line, f"{fn} takes (mask, value, lane/delta)")
            if self.mask is not None:
                raise _err(e.line, f"{fn} inside divergent control flow")
            self.uses_warp = True
            v, vt = self._expr(e.args[1])
            lane, _ = self._expr(e.args[2])
            return f"{_SHFL[fn]}({v}, {lane})", vt
        if fn in _VOTE:
            if len(e.args) != 2:
                raise _err(e.line, f"{fn} takes (mask, predicate)")
            if self.mask is not None:
                raise _err(e.line, f"{fn} inside divergent control flow")
            self.uses_warp = True
            c, t = self._expr(e.args[1])
            rt = "int" if fn == "__ballot_sync" else "bool"
            return f"{_VOTE[fn]}({self._bool(c, t)})", rt
        if fn in _ATOMICS:
            raise _err(e.line,
                       f"{fn} must stand alone as a statement or "
                       f"initialize a variable (old = {fn}(...))")
        if fn.startswith("__cast_"):
            raise _err(e.line, "C casts are out of subset (the frontend "
                               "keeps CUDA's weak literal typing)")
        raise _err(e.line, f"unknown function {fn!r}")


def translate(src: str, *, bind: dict | None = None,
              combines: dict | None = None,
              donates: tuple | None = None,
              est_block_work: float | None = None,
              name: str | None = None) -> TranslatedKernel:
    """Translate CUDA-C source into a launchable :class:`KernelDef`.

    ``bind`` maps names to Python scalars: names that are ``#define``
    macros in the source override the macro table (the frontend gate's
    ``--inject`` self-test plants a mistranslation this way); other
    names bind scalar kernel parameters (``int n``), which are inlined
    as literals.  ``combines``/``donates``/``est_block_work`` pass
    through to the :class:`KernelDef` - cross-shard merge modes and
    donation are launch-contract declarations CUDA source cannot
    express.  ``name`` picks one ``__global__`` kernel when the source
    holds several.
    """
    bind = dict(bind or {})
    macros = macro_names(src)
    lex_defines = {k: v for k, v in bind.items() if k in macros}
    scalar_bind = {k: v for k, v in bind.items() if k not in macros}
    unit = P.parse(src, lex_defines)
    if name is None:
        if len(unit.kernels) > 1:
            raise UnsupportedKernel(
                f"source defines {len(unit.kernels)} kernels "
                f"({', '.join(k.name for k in unit.kernels)}); pass "
                f"name= to pick one")
        kast = unit.kernels[0]
    else:
        match = [k for k in unit.kernels if k.name == name]
        if not match:
            raise UnsupportedKernel(
                f"no __global__ kernel named {name!r} in source (have: "
                f"{', '.join(k.name for k in unit.kernels)})")
        kast = match[0]

    tr = _Translator(kast, unit.constants, scalar_bind)
    sources, meta = tr.run()

    ns = {"jnp": jnp, "_carry": carry, "expf": expf, "logf": logf}
    stage_fns = []
    for i, stage_src in enumerate(sources):
        code = compile(stage_src, f"<cuda:{kast.name}:stage{i}>", "exec")
        exec(code, ns)
        fn = ns[f"stage_{i}"]
        fn.__cuda_source__ = stage_src
        stage_fns.append(fn)

    kw = {}
    if est_block_work is not None:
        kw["est_block_work"] = est_block_work
    kernel = KernelDef(
        kast.name, tuple(stage_fns), writes=meta["writes"],
        shared=meta["shared"], reads=meta["reads"],
        uses_warp=meta["uses_warp"], combines=dict(combines or {}),
        donates=tuple(donates or ()), **kw)
    return TranslatedKernel(
        kernel=kernel, sources=tuple(sources), cu_name=kast.name,
        params=tuple(tr.param_order), constants=tuple(tr.const_names))
