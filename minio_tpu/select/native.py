"""Native (C++) S3 Select fast path: block-streamed CSV/NDJSON scans.

The reference accelerates Select with simdjson and a generated-assembly
CSV scanner (internal/s3select/simdj/reader.go:27,
select_benchmark_test.go); this is the equivalent here — csrc/
select_scan.cpp tokenizes blocks, evaluates predicate leaves, and folds
aggregates at C speed, while this driver composes leaf masks with
numpy, keeps the cross-block aggregate state, and REPLAYS any block the
kernels flag as ambiguous through the row engine (sql.Evaluator), so
semantics match the row engine bit-for-bit even on garbage data
(whitespace-padded numbers, >2^53 ints, escaped quotes, JSON escapes,
invalid JSON lines...).

Scope (everything else falls through to the pyarrow columnar path, then
the row engine):
- CSV (single-char delim/quote, "\\n" records, no comments) or JSON
  Type=LINES; any CompressionType (blocks are read post-decompression)
- aggregate-only projections (COUNT/SUM/MIN/MAX/AVG over a column or
  COUNT(*)), or CSV `SELECT *` whose output serialization is a byte-
  passthrough of the input (same delimiter, "\\n" records, CSV output)
- WHERE: AND/OR/NOT over `col <op> literal`, LIKE, IN, BETWEEN,
  IS [NOT] NULL — the same leaf language as the columnar path

Disable with MINIO_TPU_SELECT_NATIVE=0 (MINIO_TPU_SELECT_COLUMNAR=0
disables this path too — it gates everything above the row engine).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Iterator

import numpy as np

from minio_tpu.ops import host

from . import eventstream as es
from .records import _decomp
from .sql import (AGGREGATES, Between, Bin, Cast, Col, Evaluator, Func,
                  InList, IsNull, Like, Lit, Query, SQLError, Un,
                  _cmp_pair, _num)

CHUNK = 4 << 20
FLUSH = 256 << 10
PAD = 8  # kernel SWAR parsers read up to 8 bytes past a cell

stats = {"native": 0, "fallback": 0, "replay_blocks": 0,
         # per-tier observability: bytes the native kernels consumed and
         # the subset re-decided by the Python replay (the residual-
         # replay fraction gauge in server/metrics.py is their ratio)
         "bytes_scanned": 0, "bytes_replayed": 0}

_OPS = {"=": 0, "==": 0, "!=": 1, "<>": 1, "<": 2, "<=": 3, ">": 4,
        ">=": 5}
_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
# scalar functions the C kernels evaluate per cell (csrc FN_* codes);
# non-ASCII cells flag ambiguous and replay, preserving exactness
_FN_CODES = {"lower": 1, "upper": 2, "trim": 3, "ltrim": 4, "rtrim": 5,
             "char_length": 6, "length": 6, "character_length": 6}
_FN_SUBSTR = 7

_lock = threading.Lock()
_lib = None
_lib_tried = False

_i64 = ctypes.c_int64
_dbl = ctypes.c_double
_vp = ctypes.c_void_p
_cp = ctypes.c_char_p


def _load():
    global _lib, _lib_tried
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        # the same file ops/host.py opens: one build rule, one library
        # lint: allow(blocking-under-lock): one-time native build under the dedicated dlopen lock — the lock exists to serialize exactly this init
        path = host.lib_path()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        lib.sel_csv_scan.restype = _i64
        lib.sel_csv_scan.argtypes = [
            _vp, _i64, ctypes.c_char, ctypes.c_char, ctypes.c_int, _vp,
            ctypes.c_int32, _i64, _vp, _vp, _vp, ctypes.POINTER(_i64)]
        lib.sel_cmp_num.restype = _i64
        lib.sel_cmp_num.argtypes = [
            _vp, _vp, _vp, _i64, ctypes.c_int, _dbl, _cp, ctypes.c_int32,
            _vp, ctypes.c_int, ctypes.c_int32, ctypes.c_int32]
        lib.sel_cmp_str.restype = _i64
        lib.sel_cmp_str.argtypes = [
            _vp, _vp, _vp, _i64, ctypes.c_int, _cp, ctypes.c_int32, _vp,
            ctypes.c_int, ctypes.c_int32, ctypes.c_int32]
        lib.sel_like.restype = _i64
        lib.sel_like.argtypes = [
            _vp, _vp, _vp, _i64, _cp, ctypes.c_int32, _cp, _vp,
            ctypes.c_int, ctypes.c_int32, ctypes.c_int32]
        lib.sel_cmp_expr.restype = _i64
        lib.sel_cmp_expr.argtypes = [
            _vp, _vp, _vp, _i64, ctypes.c_int, _dbl, _vp, _vp,
            ctypes.c_int, _vp]
        lib.sel_json_cmp_expr.restype = _i64
        lib.sel_json_cmp_expr.argtypes = [
            _vp, _vp, _vp, _vp, _i64, ctypes.c_int, _dbl, _vp, _vp,
            ctypes.c_int, _vp]
        lib.sel_valid.argtypes = [_vp, _i64, _vp]
        lib.sel_isnull.argtypes = [_vp, _i64, _vp]
        lib.sel_agg.restype = _i64
        lib.sel_agg.argtypes = [
            _vp, _vp, _vp, _i64, _vp, ctypes.c_int, ctypes.POINTER(_dbl),
            ctypes.POINTER(_dbl), ctypes.POINTER(_dbl),
            ctypes.POINTER(_i64), ctypes.POINTER(_i64),
            ctypes.POINTER(_i64)]
        lib.sel_emit_rows.restype = _i64
        lib.sel_emit_rows.argtypes = [
            _vp, _vp, _i64, _vp, _i64, _vp, ctypes.POINTER(_i64)]
        lib.sel_emit_cols.restype = _i64
        lib.sel_emit_cols.argtypes = [
            _vp, _vp, _vp, _i64, _vp, ctypes.c_int32, _i64, _vp, _i64,
            ctypes.c_char, _vp, ctypes.POINTER(_i64)]
        lib.sel_json_scan.restype = _i64
        lib.sel_json_scan.argtypes = [
            _vp, _i64, ctypes.c_int, _vp, _vp, ctypes.c_int32, _i64, _vp,
            _vp, _vp, _vp, _vp, ctypes.POINTER(_i64)]
        lib.sel_json_cmp.restype = _i64
        lib.sel_json_cmp.argtypes = [
            _vp, _vp, _vp, _vp, _i64, ctypes.c_int, _dbl, ctypes.c_int,
            _cp, ctypes.c_int32, _vp, ctypes.c_int, ctypes.c_int32,
            ctypes.c_int32]
        lib.sel_json_like.restype = _i64
        lib.sel_json_like.argtypes = [
            _vp, _vp, _vp, _vp, _i64, _cp, ctypes.c_int32, _cp, _vp,
            ctypes.c_int, ctypes.c_int32, ctypes.c_int32]
        lib.sel_json_valid.argtypes = [_vp, _i64, _vp]
        lib.sel_json_isnull.restype = _i64
        lib.sel_json_isnull.argtypes = [_vp, _vp, _i64, _vp]
        lib.sel_json_agg.restype = _i64
        lib.sel_json_agg.argtypes = [
            _vp, _vp, _vp, _vp, _i64, _vp, ctypes.c_int,
            ctypes.POINTER(_dbl), ctypes.POINTER(_dbl),
            ctypes.POINTER(_dbl), ctypes.POINTER(_i64),
            ctypes.POINTER(_i64), ctypes.POINTER(_i64)]
        # fused one-pass kernels (absent from pre-refactor .so builds:
        # the driver then stays on the multi-pass array path)
        try:
            lib.sel_csv_agg_fused.restype = _i64
            lib.sel_csv_agg_fused.argtypes = [
                _vp, _i64, ctypes.c_char, ctypes.c_char, ctypes.c_int,
                _vp, ctypes.c_int32,
                ctypes.c_int32, _vp, _vp, _vp, _vp, _vp, _vp, _vp, _vp,
                _vp, _cp, _cp, _vp, ctypes.c_int32, _vp, _vp,
                ctypes.c_int32, _vp, _vp, _vp, _vp, _vp, _vp, _vp, _vp,
                _vp, _vp,
                ctypes.POINTER(_i64), ctypes.POINTER(_i64),
                ctypes.POINTER(_i64), ctypes.POINTER(_i64)]
            lib.sel_json_agg_fused.restype = _i64
            lib.sel_json_agg_fused.argtypes = [
                _vp, _i64, ctypes.c_int, _vp, _vp, ctypes.c_int32,
                ctypes.c_int32, _vp, _vp, _vp, _vp, _vp, _vp, _vp, _vp,
                _vp, _vp, _cp, _cp, _vp, ctypes.c_int32, _vp, _vp,
                ctypes.c_int32, _vp, _vp, _vp, _vp, _vp, _vp, _vp, _vp,
                _vp, _vp,
                ctypes.POINTER(_i64), ctypes.POINTER(_i64),
                ctypes.POINTER(_i64)]
            lib.has_fused = True
        except AttributeError:
            lib.has_fused = False
        _lib = lib
        return _lib


def _enabled() -> bool:
    return (os.environ.get("MINIO_TPU_SELECT_NATIVE", "1") != "0"
            and os.environ.get("MINIO_TPU_SELECT_COLUMNAR", "1") != "0")


class _Fallback(Exception):
    pass


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(_vp)


# ------------------------------------------------------------ WHERE plan


def _lit_num(v) -> bool:
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and (not isinstance(v, int) or abs(v) < 2**53))


def _lit_ok(v) -> bool:
    if v is None:
        return False
    if isinstance(v, bool):
        return False  # bool literals: row-engine coercions, stay off
    if isinstance(v, int) and abs(v) >= 2**53:
        return False
    return True


def _like_plan(pat: str, esc: str | None) -> tuple[bytes, bytes]:
    """SQL LIKE pattern -> (bytes, literal-mask) for the C matcher:
    mask byte 1 = literal, 0 = wildcard role for '%'/'_'."""
    out = bytearray()
    lit = bytearray()
    i = 0
    while i < len(pat):
        c = pat[i]
        if esc and c == esc and i + 1 < len(pat):
            for b in pat[i + 1].encode():
                out.append(b)
                lit.append(1)
            i += 2
            continue
        for b in c.encode():
            out.append(b)
            lit.append(0 if c in "%_" else 1)
        i += 1
    return bytes(out), bytes(lit)


class _Plan:
    """Compiled WHERE tree: leaves call C kernels over (starts, lens[,
    types]) arrays; interior nodes compose numpy bool arrays.  `amb`
    accumulates the kernels' ambiguous-cell counts for the current
    block — nonzero means the Python replay must decide the block."""

    # Alongside the per-leaf closures, _comp records a flat "fused
    # program" (leaf descriptor rows + a postfix combiner) that the
    # one-pass kernels execute per row DURING the structural scan —
    # every leaf shape _comp accepts is expressible, so f_ok only goes
    # False on size limits (kernel fixed stacks).
    F_MAX_LEAVES = 64

    def __init__(self, where, resolve, is_json: bool):
        self.is_json = is_json
        self.cols: list = []          # resolved column ids, plan order
        self._col_of: dict = {}
        self.amb = 0
        self.f_leaves: list = []      # (kind, slot, op, isnum, fn, fa,
        #                                fb, num, aux, auxmask, expr)
        self.f_prog: list = []        # postfix: >=0 push leaf; -1 AND,
        #                                -2 OR, -3 NOT
        self.f_ok = True
        self.fn = self._comp(where, resolve) if where is not None else None

    def _f_leaf(self, kind, slot, op=0, isnum=0, fn=0, fa=0, fb=0,
                num=0.0, aux=b"", auxmask=None, expr=None) -> None:
        if not self.f_ok:
            return
        if len(self.f_leaves) >= self.F_MAX_LEAVES:
            self.f_ok = False
            return
        self.f_leaves.append((kind, slot, op, isnum, fn, fa, fb, float(num),
                              aux, auxmask, expr))
        self.f_prog.append(len(self.f_leaves) - 1)

    def _f_op(self, code: int) -> None:
        self.f_prog.append(code)

    def pack_fused(self, slot_map) -> dict | None:
        """-> ctypes-ready fused-program arrays, with plan slots
        remapped through slot_map (plan-col -> captured-cell index), or
        None when the program exceeds the kernel's fixed bounds."""
        if not self.f_ok:
            return None
        n = len(self.f_leaves)
        blob = bytearray()
        mask = bytearray()
        ecodes: list[int] = []
        eops: list[float] = []
        kind = np.zeros(n, dtype=np.int32)
        slot = np.zeros(n, dtype=np.int32)
        op = np.zeros(n, dtype=np.int32)
        isnum = np.zeros(n, dtype=np.int32)
        fn = np.zeros(n, dtype=np.int32)
        fa = np.zeros(n, dtype=np.int32)
        fb = np.zeros(n, dtype=np.int32)
        num = np.zeros(n, dtype=np.float64)
        aoff = np.zeros(n, dtype=np.int32)
        alen = np.zeros(n, dtype=np.int32)
        for i, (k, sl, o, inum, f, a, b, nv, aux, auxmask, expr) in \
                enumerate(self.f_leaves):
            kind[i] = k
            slot[i] = slot_map[sl]
            op[i] = o
            isnum[i] = inum
            fn[i] = f
            fa[i] = a
            fb[i] = b
            num[i] = nv
            if expr is not None:
                aoff[i] = len(ecodes)
                alen[i] = len(expr[0])
                ecodes.extend(expr[0])
                eops.extend(expr[1])
            else:
                aoff[i] = len(blob)
                alen[i] = len(aux)
                blob += aux
                mask += auxmask if auxmask is not None else b"\0" * len(aux)
        prog = np.array(self.f_prog, dtype=np.int32) if self.f_prog \
            else np.zeros(1, dtype=np.int32)
        return {
            "nleaves": n, "kind": kind, "slot": slot, "op": op,
            "isnum": isnum, "fn": fn, "fa": fa, "fb": fb, "num": num,
            "aoff": aoff, "alen": alen, "blob": bytes(blob),
            "mask": bytes(mask), "prog": prog,
            "prog_len": len(self.f_prog),
            "ecodes": np.array(ecodes or [0], dtype=np.int32),
            "eops": np.array(eops or [0.0], dtype=np.float64),
        }

    def _slot(self, resolved) -> int:
        if resolved not in self._col_of:
            self._col_of[resolved] = len(self.cols)
            self.cols.append(resolved)
        return self._col_of[resolved]

    def mask(self, ctx) -> np.ndarray | None:
        self.amb = 0
        if self.fn is None:
            return None
        return self.fn(ctx)

    # ctx: object with .buf (ctypes buffer), .starts/.lens/.types lists
    # of per-slot numpy arrays (length nrows), .n
    def _leaf_cmp(self, slot: int, op: str, lit_v, fn: int = 0,
                  fa: int = 0, fb: int = 0):
        lib = _load()
        opc = _OPS[op]
        numlit = _num(lit_v)
        strlit = str(lit_v).encode()
        is_num = isinstance(numlit, (int, float)) \
            and not isinstance(numlit, bool)
        if self.is_json:
            self._f_leaf(0, slot, opc, isnum=int(is_num),
                         num=float(numlit) if is_num else 0.0,
                         fn=fn, fa=fa, fb=fb, aux=strlit)
        elif is_num:
            self._f_leaf(0, slot, opc, num=float(numlit), fn=fn, fa=fa,
                         fb=fb, aux=strlit)
        else:
            self._f_leaf(1, slot, opc, fn=fn, fa=fa, fb=fb, aux=strlit)
        if self.is_json:
            def leaf(ctx):
                m = np.empty(ctx.n, dtype=np.uint8)
                self.amb += lib.sel_json_cmp(
                    ctx.buf, _ptr(ctx.starts[slot]), _ptr(ctx.lens[slot]),
                    _ptr(ctx.types[slot]), ctx.n, opc,
                    float(numlit) if is_num else 0.0, int(is_num),
                    strlit, len(strlit), _ptr(m), fn, fa, fb)
                return m.view(bool)
            return leaf
        if is_num:
            def leaf(ctx):
                m = np.empty(ctx.n, dtype=np.uint8)
                self.amb += lib.sel_cmp_num(
                    ctx.buf, _ptr(ctx.starts[slot]), _ptr(ctx.lens[slot]),
                    ctx.n, opc, float(numlit), strlit, len(strlit),
                    _ptr(m), fn, fa, fb)
                return m.view(bool)
            return leaf

        def leaf(ctx):
            m = np.empty(ctx.n, dtype=np.uint8)
            self.amb += lib.sel_cmp_str(
                ctx.buf, _ptr(ctx.starts[slot]), _ptr(ctx.lens[slot]),
                ctx.n, opc, strlit, len(strlit), _ptr(m), fn, fa, fb)
            return m.view(bool)
        return leaf

    def _num_prog(self, e):
        """Arithmetic/CAST chain over ONE column -> (Col, [(code,
        operand)]); _Fallback for anything else.  codes match csrc
        run_prog.  Literal operands must be clean numbers."""
        def walk(node):
            if isinstance(node, Col):
                return node, []
            if isinstance(node, Un) and node.op == "neg":
                col, prog = walk(node.e)
                return col, prog + [(5, 0.0)]  # 0 - x
            if isinstance(node, Cast):
                col, prog = walk(node.e)
                if node.typ in ("int", "integer"):
                    return col, prog + [(7, 0.0)]
                if node.typ in ("float", "decimal", "numeric", "double"):
                    return col, prog + [(8, 0.0)]
                raise _Fallback(f"CAST {node.typ}")
            if isinstance(node, Bin) and node.op in "+-*/%":
                code_l = {"+": 0, "-": 1, "*": 2, "/": 3, "%": 4}
                if isinstance(node.r, Lit) and _lit_num(node.r.v):
                    col, prog = walk(node.l)
                    return col, prog + [(code_l[node.op],
                                         float(_num(node.r.v)))]
                if isinstance(node.l, Lit) and _lit_num(node.l.v):
                    col, prog = walk(node.r)
                    if node.op == "+":
                        return col, prog + [(0, float(_num(node.l.v)))]
                    if node.op == "*":
                        return col, prog + [(2, float(_num(node.l.v)))]
                    if node.op == "-":
                        return col, prog + [(5, float(_num(node.l.v)))]
                    if node.op == "/":
                        return col, prog + [(6, float(_num(node.l.v)))]
                    raise _Fallback("lit % expr")
            raise _Fallback(f"expr shape {type(node).__name__}")

        col, prog = walk(e)
        if not prog:
            raise _Fallback("bare column")  # plain cmp path handles it
        return col, prog

    def _leaf_expr(self, e, resolve, op: str, lit_v):
        """expr(col) <op> numeric-literal leaf via sel_cmp_expr."""
        numlit = _num(lit_v)
        if not _lit_num(numlit):
            raise _Fallback("expr vs text literal")  # str() rendering
        col, prog = self._num_prog(e)
        slot = self._slot(resolve(col.name))
        lib = _load()
        opc = _OPS[op]
        codes = np.array([c for c, _ in prog], dtype=np.int32)
        ops = np.array([o for _, o in prog], dtype=np.float64)
        self._f_leaf(5, slot, opc, num=float(numlit),
                     expr=([c for c, _ in prog], [o for _, o in prog]))
        isj = self.is_json

        def leaf(ctx, slot=slot, codes=codes, ops=ops):
            m = np.empty(ctx.n, dtype=np.uint8)
            if isj:
                self.amb += lib.sel_json_cmp_expr(
                    ctx.buf, _ptr(ctx.starts[slot]), _ptr(ctx.lens[slot]),
                    _ptr(ctx.types[slot]), ctx.n, opc, float(numlit),
                    _ptr(codes), _ptr(ops), len(prog), _ptr(m))
            else:
                self.amb += lib.sel_cmp_expr(
                    ctx.buf, _ptr(ctx.starts[slot]), _ptr(ctx.lens[slot]),
                    ctx.n, opc, float(numlit), _ptr(codes), _ptr(ops),
                    len(prog), _ptr(m))
            return m.view(bool)
        return leaf

    def _col_fn(self, e, resolve):
        """Col or fn(Col[, args]) -> (slot, fn_code, fn_a, fn_b);
        _Fallback otherwise."""
        if isinstance(e, Col):
            return self._slot(resolve(e.name)), 0, 0, 0
        if isinstance(e, Func) and e.name in _FN_CODES \
                and len(e.args) == 1 and isinstance(e.args[0], Col):
            return (self._slot(resolve(e.args[0].name)),
                    _FN_CODES[e.name], 0, 0)
        if isinstance(e, Func) and e.name == "substring" \
                and 2 <= len(e.args) <= 3 \
                and isinstance(e.args[0], Col) \
                and all(isinstance(a, Lit) and isinstance(a.v, int)
                        and not isinstance(a.v, bool)
                        and abs(a.v) < 2**31 for a in e.args[1:]):
            start = int(e.args[1].v)
            if len(e.args) > 2:
                ln = int(e.args[2].v)
                if ln < 0:
                    # explicit negative lengths have Python-slice
                    # semantics in the row engine; -1 is also the
                    # internal 'to end' sentinel — never conflate them
                    raise _Fallback("negative SUBSTRING length")
            else:
                ln = -1  # sentinel: slice to end
            return (self._slot(resolve(e.args[0].name)), _FN_SUBSTR,
                    start, ln)
        raise _Fallback(f"unsupported operand {type(e).__name__}")

    def _valid(self, slot: int):
        lib = _load()
        if self.is_json:
            def v(ctx):
                m = np.empty(ctx.n, dtype=np.uint8)
                lib.sel_json_valid(_ptr(ctx.types[slot]), ctx.n, _ptr(m))
                return m.view(bool)
            return v

        def v(ctx):
            m = np.empty(ctx.n, dtype=np.uint8)
            lib.sel_valid(_ptr(ctx.lens[slot]), ctx.n, _ptr(m))
            return m.view(bool)
        return v

    def _comp(self, e, resolve):
        lib = _load()
        if isinstance(e, Un):
            if e.op != "not":
                raise _Fallback("unary " + e.op)
            inner = self._comp(e.e, resolve)
            self._f_op(-3)
            return lambda ctx: ~inner(ctx)
        if isinstance(e, Bin) and e.op in ("and", "or"):
            lf, rf = self._comp(e.l, resolve), self._comp(e.r, resolve)
            self._f_op(-1 if e.op == "and" else -2)
            if e.op == "and":
                return lambda ctx: lf(ctx) & rf(ctx)
            return lambda ctx: lf(ctx) | rf(ctx)
        if isinstance(e, Like):
            if not (isinstance(e.pat, Lit)
                    and isinstance(e.pat.v, str)
                    and (e.esc is None or (isinstance(e.esc, Lit)
                                           and isinstance(e.esc.v, str)))):
                raise _Fallback("LIKE shape")
            slot, fncode, fa, fb = self._col_fn(e.e, resolve)
            if fncode == _FN_CODES["char_length"]:
                raise _Fallback("LIKE over CHAR_LENGTH")
            pat, litmask = _like_plan(
                str(e.pat.v), str(e.esc.v) if e.esc is not None else None)
            negate = e.negate
            validf = self._valid(slot)
            self._f_leaf(2, slot, fn=fncode, fa=fa, fb=fb, aux=pat,
                         auxmask=litmask)
            if negate:
                # null cells make LIKE and NOT LIKE both false
                self._f_op(-3)
                self._f_leaf(4, slot)
                self._f_op(-1)
            fn = lib.sel_json_like if self.is_json else lib.sel_like

            def leaf(ctx, slot=slot, pat=pat, litmask=litmask,
                     negate=negate, fn=fn, fncode=fncode, fa=fa, fb=fb):
                m = np.empty(ctx.n, dtype=np.uint8)
                if self.is_json:
                    self.amb += fn(ctx.buf, _ptr(ctx.starts[slot]),
                                   _ptr(ctx.lens[slot]),
                                   _ptr(ctx.types[slot]), ctx.n,
                                   pat, len(pat), litmask, _ptr(m),
                                   fncode, fa, fb)
                else:
                    self.amb += fn(ctx.buf, _ptr(ctx.starts[slot]),
                                   _ptr(ctx.lens[slot]), ctx.n,
                                   pat, len(pat), litmask, _ptr(m),
                                   fncode, fa, fb)
                mb = m.view(bool)
                # null cells make LIKE and NOT LIKE both false
                return (validf(ctx) & ~mb) if negate else mb
            return leaf
        if isinstance(e, InList):
            if not all(isinstance(x, Lit) and _lit_ok(x.v)
                       for x in e.items):
                raise _Fallback("IN shape")
            slot, fncode, fa, fb = self._col_fn(e.e, resolve)
            leaves = [self._leaf_cmp(slot, "=", x.v, fncode, fa, fb)
                      for x in e.items]
            for _ in e.items[1:]:
                self._f_op(-2)
            validf = self._valid(slot)
            negate = e.negate
            if negate:
                self._f_op(-3)
                self._f_leaf(4, slot)
                self._f_op(-1)

            def leaf(ctx, leaves=leaves, negate=negate):
                m = leaves[0](ctx)
                for lf in leaves[1:]:
                    m = m | lf(ctx)
                return (validf(ctx) & ~m) if negate else m
            return leaf
        if isinstance(e, Between):
            if not (isinstance(e.lo, Lit) and _lit_ok(e.lo.v)
                    and isinstance(e.hi, Lit) and _lit_ok(e.hi.v)):
                raise _Fallback("BETWEEN shape")
            slot, fncode, fa, fb = self._col_fn(e.e, resolve)
            lo = self._leaf_cmp(slot, ">=", e.lo.v, fncode, fa, fb)
            hi = self._leaf_cmp(slot, "<=", e.hi.v, fncode, fa, fb)
            self._f_op(-1)
            validf = self._valid(slot)
            negate = e.negate
            if negate:
                self._f_op(-3)
                self._f_leaf(4, slot)
                self._f_op(-1)

            def leaf(ctx, lo=lo, hi=hi, negate=negate):
                m = lo(ctx) & hi(ctx)
                return (validf(ctx) & ~m) if negate else m
            return leaf
        if isinstance(e, IsNull):
            if not isinstance(e.e, Col):
                raise _Fallback("IS NULL shape")
            slot = self._slot(resolve(e.e.name))
            negate = e.negate
            isj = self.is_json
            self._f_leaf(3, slot)
            if negate:
                self._f_op(-3)

            def leaf(ctx, slot=slot, negate=negate):
                m = np.empty(ctx.n, dtype=np.uint8)
                if isj:
                    self.amb += lib.sel_json_isnull(
                        _ptr(ctx.lens[slot]), _ptr(ctx.types[slot]),
                        ctx.n, _ptr(m))
                else:
                    lib.sel_isnull(_ptr(ctx.lens[slot]), ctx.n, _ptr(m))
                mb = m.view(bool)
                return ~mb if negate else mb
            return leaf
        if isinstance(e, Bin) and e.op in ("=", "==", "!=", "<>", "<",
                                           "<=", ">", ">="):
            def fold_neg(node):
                # the parser renders -900 as Un(neg, Lit(900))
                if isinstance(node, Un) and node.op == "neg" \
                        and isinstance(node.e, Lit) \
                        and isinstance(node.e.v, (int, float)) \
                        and not isinstance(node.e.v, bool):
                    return Lit(-node.e.v)
                return node

            col, lit, flip = e.l, fold_neg(e.r), False
            if isinstance(fold_neg(e.l), Lit):
                col, lit, flip = e.r, fold_neg(e.l), True
            if not (isinstance(lit, Lit) and _lit_ok(lit.v)):
                raise _Fallback("cmp shape")
            op = _FLIP.get(e.op, e.op) if flip else e.op
            try:
                slot, fn, fa, fb = self._col_fn(col, resolve)
            except _Fallback:
                # arithmetic / CAST chain over one column
                return self._leaf_expr(col, resolve, op, lit.v)
            return self._leaf_cmp(slot, op, lit.v, fn, fa, fb)
        raise _Fallback(f"unsupported node {type(e).__name__}")


# --------------------------------------------------------------- shapes


def _agg_shape(q: Query):
    """-> list of (what, colname|None, func) or None.  what: 0 COUNT,
    1 SUM/AVG, 2 MIN/MAX."""
    if q.star or not q.projections:
        return None
    out = []
    for p in q.projections:
        f = p.expr
        if not (isinstance(f, Func) and f.name in AGGREGATES):
            return None
        if f.star:
            out.append((0, None, f.name))
            continue
        if len(f.args) != 1 or not isinstance(f.args[0], Col):
            return None
        what = 0 if f.name == "count" else (
            1 if f.name in ("sum", "avg") else 2)
        out.append((what, f.args[0].name, f.name))
    return out


def _alias_strip(name: str, alias: str) -> str:
    parts = name.split(".")
    if alias and parts and parts[0].lower() == alias:
        parts = parts[1:]
    if len(parts) != 1:
        raise _Fallback(f"nested column {name}")
    return parts[0]


class _Ctx:
    pass


class _Blocks:
    """Block feeder for the scan generators.

    Arena mode: stream bytes are readinto() a reusable padded bytearray
    (ONE copy — the old read()-then-stage path made two, and at fused-
    scan rates each extra memory pass costs as much as the scan
    itself).  Direct mode (fused aggregate queries over uncompressed
    memory-resident sources): segments of the source buffer go to the
    kernels zero-copy; a record crossing a segment boundary is simply
    re-scanned from its start (consumed semantics), and the final
    segment always goes through the arena so the kernels' 8-byte SWAR
    overread stays inside owned memory.
    """

    SEG = 16 << 20

    def __init__(self, raw, rw, leftover: bytes, compression: str,
                 direct_ok: bool):
        self.raw = raw
        self.tail = leftover or b""
        self.ba = bytearray(CHUNK + (1 << 20) + PAD)
        self.base = (ctypes.c_char * len(self.ba)).from_buffer(self.ba)
        self.dnp = None
        self.dpos = 0
        self._direct_blk = False
        self._blen = 0
        if direct_ok and (compression or "NONE").upper() in ("NONE", "") \
                and raw is rw:
            mv = rw.direct_buffer()
            if mv is not None and len(mv) > 0:
                self._mv = mv  # keeps the source export alive
                self.dnp = np.frombuffer(mv, dtype=np.uint8)

    def _grow(self, blen: int) -> None:
        if blen + PAD > len(self.ba):
            self.base = None
            self.ba = bytearray(blen * 2 + PAD)
            self.base = (ctypes.c_char * len(self.ba)).from_buffer(
                self.ba)

    def _stage(self, data: bytes, final: bool):
        if len(data) > (64 << 20):
            raise SQLError("record too large")
        blen = len(data)
        self._grow(blen)
        self.ba[:blen] = data
        self.ba[blen:blen + PAD] = b"\0" * PAD
        self.tail = b""
        self._direct_blk = False
        self._blen = blen
        return (ctypes.addressof(self.base), blen, final)

    def _find_nl(self, pos: int) -> int:
        d = self.dnp
        w = 1 << 16
        while True:
            end = min(pos + w, len(d))
            hits = np.flatnonzero(d[pos:end] == 10)
            if len(hits):
                return pos + int(hits[0])
            if end >= len(d):
                return -1
            w *= 16

    def next(self):
        """-> (base_address, block_len, final) or None at end."""
        d = self.dnp
        if d is not None:
            L = len(d)
            pos = self.dpos
            if pos >= L:
                self.dnp = None
                if self.tail:
                    return self._stage(self.tail, True)
                return None
            if self.tail:
                # stitch: complete the pending partial record with
                # bytes up to (and including) the next newline
                nl = self._find_nl(pos)
                if nl < 0:
                    self.dnp = None
                    self.dpos = L
                    return self._stage(
                        self.tail + d[pos:].tobytes(), True)
                data = self.tail + d[pos:nl + 1].tobytes()
                self.dpos = nl + 1
                return self._stage(data, False)
            rem = L - pos
            if rem > (1 << 16):
                # direct segment; always leave a staged tail so the
                # kernels' SWAR overread stays inside owned memory
                seg = min(self.SEG, rem - 4096)
                self._direct_blk = True
                self._blen = seg
                return (self.dnp.ctypes.data + pos, seg, False)
            self.dnp = None
            self.dpos = L
            return self._stage(d[pos:].tobytes(), True)
        # arena mode
        tlen = len(self.tail)
        self._grow(tlen + CHUNK)
        if tlen:
            self.ba[:tlen] = self.tail
            self.tail = b""
        got = self.raw.readinto(
            memoryview(self.ba)[tlen:tlen + CHUNK]) or 0
        blen = tlen + got
        if blen == 0:
            return None
        self.ba[blen:blen + PAD] = b"\0" * PAD
        self._direct_blk = False
        self._blen = blen
        return (ctypes.addressof(self.base), blen, got == 0)

    def view(self, off: int = 0):
        """Buffer view of the current block from `off` (for replay)."""
        if self._direct_blk:
            return self.dnp[self.dpos + off:self.dpos + self._blen]
        return memoryview(self.ba)[off:]

    def find(self, needle: bytes, a: int, b: int) -> int:
        """byte search within the current block (arena blocks only —
        direct blocks exist only on fused paths, which detect quotes
        in-kernel)."""
        if self._direct_blk:
            return -1
        return self.ba.find(needle, a, b)

    def advance(self, off: int) -> None:
        """Consume `off` bytes of the current block; the rest becomes
        the pending tail for the next one."""
        if self._direct_blk:
            if off == 0:
                # record longer than a direct segment: fall back to
                # stitched arena staging for this record
                self.tail = self.dnp[
                    self.dpos:self.dpos + self._blen].tobytes()
                self.dpos += self._blen
            else:
                self.dpos += off
            return
        blen = self._blen
        if off < blen:
            self.tail = bytes(self.ba[off:blen])
            if len(self.tail) > (64 << 20):
                raise SQLError("record too large")


# ------------------------------------------------------------- CSV path


def _csv_opts(req):
    inp = req.input_ser
    c = inp["CSV"] if isinstance(inp["CSV"], dict) else {}
    delim = c.get("FieldDelimiter", ",") or ","
    quote = c.get("QuoteCharacter", '"') or '"'
    header = (c.get("FileHeaderInfo", "USE") or "USE").upper()
    if (c.get("RecordDelimiter", "\n") or "\n") != "\n":
        raise _Fallback("record delimiter")
    if len(delim) != 1 or len(quote) != 1 or delim == quote:
        raise _Fallback("delim/quote")
    if c.get("Comments"):
        raise _Fallback("comments")
    return delim, quote, header


def _read_header(raw, quote: str) -> tuple[bytes, bytes]:
    """-> (header_line_without_newline, leftover buffered bytes).
    Falls back when the first line contains the quote char (quoted or
    multi-line headers: rare, pyarrow handles them)."""
    buf = b""
    while b"\n" not in buf:
        chunk = raw.read(65536)
        if not chunk:
            break
        buf += chunk
        if len(buf) > (1 << 20):
            raise _Fallback("header line too long")
    if b"\n" not in buf:
        return buf, b""
    line, rest = buf.split(b"\n", 1)
    if quote.encode() in line:
        raise _Fallback("quoted header")
    return line, rest


def _try_csv(req, query: Query, rw, object_size: int, out):
    delim, quote, header = _csv_opts(req)
    compression = req.input_ser.get("CompressionType", "NONE") or "NONE"
    aggs = _agg_shape(query)
    emit = False
    proj_cols_ast: list | None = None
    if aggs is None:
        # SELECT * passthrough, or plain-column projections, both with
        # CSV output whose serialization matches the input (cells copy
        # verbatim; quoted/\r blocks replay through the row engine)
        o = req.output_ser
        oc = o.get("CSV")
        if not isinstance(oc, (dict, type(None))) or "CSV" not in o:
            raise _Fallback("output serialization")
        oc = oc if isinstance(oc, dict) else {}
        if (oc.get("FieldDelimiter", ",") or ",") != delim \
                or (oc.get("RecordDelimiter", "\n") or "\n") != "\n" \
                or (oc.get("QuoteCharacter", '"') or '"') != '"':
            raise _Fallback("output serialization")
        if query.star and not query.projections:
            emit = True
        elif query.projections and all(
                isinstance(p.expr, Col) for p in query.projections):
            # the row engine projects into a DICT: duplicate output
            # names collapse to one column — fall back for that shape
            names_out = [p.alias or Evaluator._auto_name(p.expr, i)
                         for i, p in enumerate(query.projections)]
            if len(set(names_out)) != len(names_out):
                raise _Fallback("duplicate projection names")
            proj_cols_ast = [p.expr for p in query.projections]
            emit = True
        else:
            raise _Fallback("projection shape")

    raw = _decomp(rw, compression)
    if header == "USE":
        hline, leftover = _read_header(raw, quote)
        try:
            names = [h.strip() for h in
                     hline.decode("utf-8", "replace").split(delim)]
        except Exception:
            raise _Fallback("header decode")
        if hline.strip() == b"":
            names = []
    elif header == "IGNORE":
        hline, leftover = _read_header(raw, quote)
        names = []
    else:
        names = []
        leftover = b""

    def resolve(name: str) -> int:
        import re as re_mod

        p = _alias_strip(name, query.table_alias)
        if header == "USE" and names:
            if p in names:
                return names.index(p)
            lowered = [s.lower() for s in names]
            if p.lower() in lowered:
                return lowered.index(p.lower())
        if re_mod.fullmatch(r"_\d+", p):
            i = int(p[1:]) - 1
            if i >= 0 and (not names or i < len(names)):
                return i
        raise _Fallback(f"unknown column {name}")

    plan = _Plan(query.where, resolve, is_json=False)
    agg_cols: list[int | None] = []
    if aggs is not None:
        for what, colname, fname in aggs:
            agg_cols.append(None if colname is None
                            else resolve(colname))
    proj_resolved: list[int] = []
    if proj_cols_ast is not None:
        proj_resolved = [resolve(c.name) for c in proj_cols_ast]

    # needed columns, ascending, plus slot remap
    needed = sorted(set(plan.cols) | {c for c in agg_cols
                                      if c is not None}
                    | set(proj_resolved)) or [0]
    col_pos = {c: i for i, c in enumerate(needed)}
    ev = Evaluator(query)
    lib = _load()
    if lib is None:
        raise _Fallback("native lib unavailable")
    stats["native"] += 1
    rw.commit()
    keys = [(names[i] if names and i < len(names) and names[i]
             else f"_{i + 1}") for i in range(len(names))] if names else []

    # fused one-pass program: aggregate queries whose WHERE compiled and
    # whose working set fits the kernel's fixed cell registers run scan
    # + predicate + fold in a single traversal (quote-free blocks only —
    # a quoted block falls back to the multi-pass array kernels below)
    fused = None
    f_aggs = None
    if aggs is not None and getattr(lib, "has_fused", False) \
            and len(needed) <= 16:
        fused = plan.pack_fused([col_pos[c] for c in plan.cols])
        if fused is not None:
            f_aggs = {
                "what": np.array([w for w, _, _ in aggs],
                                 dtype=np.int32),
                "slot": np.array([-1 if c is None else col_pos[c]
                                  for c in agg_cols], dtype=np.int32),
            }

    def replay_rows(block: bytes, a: int, b: int, collect=None) -> None:
        """Row-engine evaluation of block[a:b] (complete records)."""
        import csv as csv_mod
        import io as io_mod

        stats["replay_blocks"] += 1
        stats["bytes_replayed"] += b - a
        text = bytes(block[a:b]).decode("utf-8", "replace")
        rdr = csv_mod.reader(io_mod.StringIO(text), delimiter=delim,
                             quotechar=quote)
        for rowvals in rdr:
            if not rowvals:
                continue
            if keys:
                rec = {}
                for i, v in enumerate(rowvals):
                    kk = keys[i] if i < len(keys) else f"_{i + 1}"
                    rec[kk] = v
            else:
                rec = {f"_{i + 1}": v for i, v in enumerate(rowvals)}
            if collect is not None:
                if ev.matches(rec):
                    collect(rec)
            elif ev.matches(rec):
                ev.accumulate(rec)

    def emit_collect(rec, sink, limiter):
        # replayed rows re-serialize through the row-engine writer so
        # quoted cells round-trip exactly as the slow path would
        if limiter[0] is not None and limiter[1] >= limiter[0]:
            return
        sink += out.serialize(ev.project(rec))
        limiter[1] += 1

    def gen() -> Iterator[bytes]:
        max_rows = 1 << 19
        col_arr = np.array(needed, dtype=np.int32)
        slots_arr = np.array([col_pos[c] for c in proj_resolved],
                             dtype=np.int32)
        # capacity math: a cell's bytes are emitted ONCE PER SLOT that
        # references its column (SELECT a AS x, a AS y re-emits a), so
        # the bound scales by the max per-column multiplicity
        from collections import Counter

        emit_mult = max(Counter(proj_resolved).values(), default=1)
        starts = np.empty((len(needed), max_rows), dtype=np.int32)
        lens = np.empty((len(needed), max_rows), dtype=np.int32)
        row_start = np.empty(max_rows + 1, dtype=np.int32)
        consumed = _i64()
        out_len = _i64()
        naggs = len(aggs) if aggs is not None else 0
        agg_cnt = np.zeros(naggs, dtype=np.int64)
        agg_s = np.zeros(naggs, dtype=np.float64)
        agg_mn = np.zeros(naggs, dtype=np.float64)
        agg_mx = np.zeros(naggs, dtype=np.float64)
        agg_mnp = np.zeros(naggs, dtype=np.int32)
        agg_mnl = np.zeros(naggs, dtype=np.int32)
        agg_mxp = np.zeros(naggs, dtype=np.int32)
        agg_mxl = np.zeros(naggs, dtype=np.int32)
        rows_o = _i64()
        amb_o = _i64()
        emit_buf = ctypes.create_string_buffer(CHUNK + (1 << 16)) \
            if emit else None
        saw_q = _i64()
        returned = 0
        outbuf = bytearray()
        limit = query.limit
        n_out = 0
        qb = quote.encode()
        # emit verbatim only when no cell could force the row-engine
        # writer to quote: input quote char, OUTPUT quote char (they
        # can differ — a cell may contain '"' while the input quote is
        # "'"), or a bare \r
        emit_guards = {qb, b'"', b"\r"}
        feeder = _Blocks(raw, rw, leftover, compression,
                         direct_ok=fused is not None)
        skip_fused = False  # quoted stretch pending: array path decides
        try:
            while True:
                blk = feeder.next()
                if blk is None:
                    break
                addr, blen, final = blk
                if emit and limit is not None and n_out >= limit:
                    break
                off = 0
                while off < blen:
                    seg_len = blen - off
                    pad = feeder.view(off)
                    cbuf = _vp(addr + off)
                    if fused is not None and not skip_fused:
                        lib.sel_csv_agg_fused(
                            cbuf, seg_len, delim.encode(), qb,
                            1 if final else 0, _ptr(col_arr),
                            len(needed), fused["nleaves"],
                            _ptr(fused["kind"]), _ptr(fused["slot"]),
                            _ptr(fused["op"]), _ptr(fused["fn"]),
                            _ptr(fused["fa"]), _ptr(fused["fb"]),
                            _ptr(fused["num"]), _ptr(fused["aoff"]),
                            _ptr(fused["alen"]), fused["blob"],
                            fused["mask"], _ptr(fused["prog"]),
                            fused["prog_len"], _ptr(fused["ecodes"]),
                            _ptr(fused["eops"]), naggs,
                            _ptr(f_aggs["what"]), _ptr(f_aggs["slot"]),
                            _ptr(agg_cnt), _ptr(agg_s), _ptr(agg_mn),
                            _ptr(agg_mx), _ptr(agg_mnp), _ptr(agg_mnl),
                            _ptr(agg_mxp), _ptr(agg_mxl),
                            ctypes.byref(rows_o), ctypes.byref(amb_o),
                            ctypes.byref(consumed), ctypes.byref(saw_q))
                        cons = int(consumed.value)
                        stats["bytes_scanned"] += cons
                        if amb_o.value > 0:
                            replay_rows(pad, 0, cons)
                        else:
                            results = []
                            for ai, (what, colname, fname) in \
                                    enumerate(aggs):
                                if agg_cols[ai] is None:
                                    results.append(
                                        ("count", int(agg_cnt[ai]), 0.0,
                                         None, None))
                                    continue
                                lo = hi = None
                                if what == 2 and int(agg_mnl[ai]) >= 0:
                                    a0 = int(agg_mnp[ai])
                                    l0 = int(agg_mnl[ai])
                                    lo = _num(bytes(pad[a0:a0 + l0])
                                              .decode("utf-8", "replace"))
                                    a1 = int(agg_mxp[ai])
                                    l1 = int(agg_mxl[ai])
                                    hi = _num(bytes(pad[a1:a1 + l1])
                                              .decode("utf-8", "replace"))
                                results.append((fname, int(agg_cnt[ai]),
                                                float(agg_s[ai]), lo, hi))
                            _commit_agg(ev, results)
                        off += cons
                        if int(saw_q.value):
                            skip_fused = True
                            continue
                        if cons == 0:
                            break
                        continue
                    n = lib.sel_csv_scan(
                        cbuf, seg_len, delim.encode(), quote.encode(),
                        1 if final else 0, _ptr(col_arr), len(needed),
                        max_rows, _ptr(starts), _ptr(lens),
                        _ptr(row_start), ctypes.byref(consumed))
                    skip_fused = False  # quoted stretch now consumed
                    if n == -2:
                        # unterminated quote at EOF: Python's csv module
                        # yields the open field as-is — replay exactly
                        if emit:
                            lim = [limit, n_out]
                            replay_rows(pad, 0, seg_len,
                                        collect=lambda rec: emit_collect(
                                            rec, outbuf, lim))
                            n_out = lim[1]
                        else:
                            replay_rows(pad, 0, seg_len)
                        stats["bytes_scanned"] += seg_len
                        off = blen
                        break
                    if n == 0:
                        break  # need more data
                    n = int(n)
                    ctx = _Ctx()
                    ctx.buf = cbuf
                    ctx.n = n
                    ctx.starts = [starts[col_pos[c], :n]
                                  for c in plan.cols]
                    ctx.lens = [lens[col_pos[c], :n] for c in plan.cols]
                    mask = plan.mask(ctx)
                    ambiguous = plan.amb > 0
                    if not ambiguous and aggs is not None:
                        # run every aggregate kernel BEFORE committing
                        # any state: a later kernel may turn up amb
                        results = []
                        kmask = None
                        if mask is not None:
                            kmask = np.ascontiguousarray(
                                mask.astype(np.uint8))
                        for (what, colname, fname), rcol in zip(
                                aggs, agg_cols):
                            if rcol is None:
                                results.append(
                                    ("count",
                                     int(mask.sum()) if mask is not None
                                     else n, 0.0, None, None))
                                continue
                            s = _dbl()
                            mn = _dbl()
                            mx = _dbl()
                            am = _i64()
                            ax = _i64()
                            ab = _i64()
                            sl = col_pos[rcol]
                            cnt = lib.sel_agg(
                                cbuf, _ptr(starts[sl, :n]),
                                _ptr(lens[sl, :n]), n,
                                _ptr(kmask) if kmask is not None
                                else None,
                                what, ctypes.byref(s), ctypes.byref(mn),
                                ctypes.byref(mx), ctypes.byref(am),
                                ctypes.byref(ax), ctypes.byref(ab))
                            if ab.value > 0:
                                ambiguous = True
                                break
                            lo = hi = None
                            if what == 2 and am.value >= 0:
                                a0 = int(starts[sl, am.value])
                                l0 = int(lens[sl, am.value])
                                lo = _num(bytes(pad[a0:a0 + l0]).decode(
                                    "utf-8", "replace"))
                                a1 = int(starts[sl, ax.value])
                                l1 = int(lens[sl, ax.value])
                                hi = _num(bytes(pad[a1:a1 + l1]).decode(
                                    "utf-8", "replace"))
                            results.append((fname, int(cnt),
                                            float(s.value), lo, hi))
                        if not ambiguous:
                            _commit_agg(ev, results)
                    if emit and not ambiguous and any(
                            feeder.find(g, off,
                                        off + int(consumed.value)) >= 0
                            for g in emit_guards):
                        # quoted cells (input OR output quote char),
                        # or bare \r, don't round-trip verbatim: the
                        # row-engine writer re-quotes — replay this
                        # batch through it
                        ambiguous = True
                    if ambiguous:
                        if emit:
                            lim = [limit, n_out]
                            replay_rows(pad, 0, int(consumed.value),
                                        collect=lambda rec: emit_collect(
                                            rec, outbuf, lim))
                            n_out = lim[1]
                        else:
                            replay_rows(pad, 0, int(consumed.value))
                    elif emit:
                        km = None
                        if mask is not None:
                            km = np.ascontiguousarray(
                                mask.astype(np.uint8))
                        lim = -1 if limit is None else max(
                            0, limit - n_out)
                        # emitted bytes bound: every cell emits once
                        # per slot referencing its column, plus per-row
                        # separators/newline
                        need_cap = int(consumed.value) * emit_mult + \
                            1 + n * (len(proj_resolved) + 2)
                        if need_cap > ctypes.sizeof(emit_buf):
                            emit_buf = ctypes.create_string_buffer(
                                need_cap * 2)
                        if proj_cols_ast is None:
                            wrote = lib.sel_emit_rows(
                                cbuf, _ptr(row_start[:n + 1]), n,
                                _ptr(km) if km is not None else None,
                                lim, emit_buf, ctypes.byref(out_len))
                        else:
                            wrote = lib.sel_emit_cols(
                                cbuf, _ptr(starts), _ptr(lens),
                                max_rows, _ptr(slots_arr),
                                len(proj_resolved), n,
                                _ptr(km) if km is not None else None,
                                lim, delim.encode(), emit_buf,
                                ctypes.byref(out_len))
                        n_out += int(wrote)
                        if out_len.value:
                            outbuf += emit_buf.raw[:out_len.value]
                            while len(outbuf) >= FLUSH:
                                returned += FLUSH
                                yield es.records_message(
                                    bytes(outbuf[:FLUSH]))
                                del outbuf[:FLUSH]
                        if limit is not None and n_out >= limit:
                            break
                    stats["bytes_scanned"] += int(consumed.value)
                    off += int(consumed.value)
                    if int(consumed.value) == 0:
                        break
                feeder.advance(off)
                if final:
                    break
            if aggs is not None:
                outbuf += out.serialize(ev.aggregate_result())
            if outbuf:
                returned += len(outbuf)
                yield es.records_message(bytes(outbuf))
            if req.request_progress:
                yield es.progress_message(object_size, object_size,
                                          returned)
            yield es.stats_message(object_size, object_size, returned)
            yield es.end_message()
        except SQLError as e:
            yield es.error_message("InvalidQuery", str(e))

    return gen()


def _commit_agg(ev: Evaluator, results) -> None:
    for i, (fname, cnt, s, lo, hi) in enumerate(results):
        st = ev._agg_state[i]
        st["count"] += cnt
        if fname in ("sum", "avg"):
            st["sum"] += s
        if fname in ("min", "max") and lo is not None:
            if st["min"] is None:
                st["min"], st["max"] = lo, hi
            else:
                a, b = _cmp_pair(lo, st["min"])
                if a < b:
                    st["min"] = lo
                a, b = _cmp_pair(hi, st["max"])
                if a > b:
                    st["max"] = hi


# ------------------------------------------------------------ JSON path


def _try_json(req, query: Query, rw, object_size: int, out):
    j = req.input_ser["JSON"] if isinstance(req.input_ser["JSON"], dict) \
        else {}
    if (j.get("Type", "DOCUMENT") or "DOCUMENT").upper() != "LINES":
        raise _Fallback("JSON type")
    aggs = _agg_shape(query)
    if aggs is None:
        raise _Fallback("projection shape")  # pyarrow handles these
    compression = req.input_ser.get("CompressionType", "NONE") or "NONE"
    raw = _decomp(rw, compression)

    keymap: dict[str, int] = {}

    def resolve(name: str) -> str:
        p = _alias_strip(name, query.table_alias)
        return p

    plan = _Plan(query.where, resolve, is_json=True)
    agg_keys: list[str | None] = []
    for what, colname, fname in aggs:
        agg_keys.append(None if colname is None
                        else resolve(colname))
    all_keys = list(dict.fromkeys(
        [k for k in plan.cols] + [k for k in agg_keys if k is not None]))
    if not all_keys:
        all_keys = ["\x00none"]  # dummy slot: bad-line detection only
    for i, k in enumerate(all_keys):
        keymap[k] = i
    ev = Evaluator(query)
    lib = _load()
    if lib is None:
        raise _Fallback("native lib unavailable")
    stats["native"] += 1
    rw.commit()

    # fused one-pass program (parse + predicate + fold per line); the
    # array path below remains for programs past the kernel bounds
    fused = None
    f_aggs = None
    if getattr(lib, "has_fused", False) and len(all_keys) <= 16:
        fused = plan.pack_fused([keymap[k] for k in plan.cols])
        if fused is not None:
            f_aggs = {
                "what": np.array([w for w, _, _ in aggs],
                                 dtype=np.int32),
                "slot": np.array([-1 if k is None else keymap[k]
                                  for k in agg_keys], dtype=np.int32),
            }

    def _replay_line(json_mod, line: bytes) -> None:
        text = line.decode("utf-8", "replace")
        try:
            doc = json_mod.loads(text)
        except ValueError as e:
            raise SQLError(f"invalid JSON line: {e}")
        rec = doc if isinstance(doc, dict) else {"_1": doc}
        if ev.matches(rec):
            ev.accumulate(rec)

    def replay_rows(pad: bytes, rs: np.ndarray, rl: np.ndarray,
                    rows: np.ndarray) -> None:
        import json as json_mod

        stats["replay_blocks"] += 1
        for r in rows:
            stats["bytes_replayed"] += int(rl[r])
            _replay_line(json_mod, bytes(pad[rs[r]:rs[r] + rl[r]]))

    def replay_span(pad, nbytes: int) -> None:
        """Replay a fused-scan span: same per-line semantics as
        replay_rows, with line splitting done here (the fused kernel
        materializes no row-extent arrays)."""
        import json as json_mod

        stats["replay_blocks"] += 1
        stats["bytes_replayed"] += nbytes
        for raw_line in bytes(pad[:nbytes]).split(b"\n"):
            line = raw_line.strip(b" \t\r")
            if line:
                _replay_line(json_mod, line)

    def gen() -> Iterator[bytes]:
        max_rows = 1 << 18
        nk = len(all_keys)
        kbytes = [k.encode() for k in all_keys]
        keys_arr = (ctypes.c_char_p * nk)(*kbytes)
        key_lens = np.array([len(b) for b in kbytes], dtype=np.int32)
        starts = np.empty((nk, max_rows), dtype=np.int32)
        lens = np.empty((nk, max_rows), dtype=np.int32)
        types = np.empty((nk, max_rows), dtype=np.uint8)
        row_start = np.empty(max_rows + 1, dtype=np.int32)
        row_len = np.empty(max_rows, dtype=np.int32)
        consumed = _i64()
        naggs = len(aggs)
        agg_cnt = np.zeros(naggs, dtype=np.int64)
        agg_s = np.zeros(naggs, dtype=np.float64)
        agg_mn = np.zeros(naggs, dtype=np.float64)
        agg_mx = np.zeros(naggs, dtype=np.float64)
        agg_mnp = np.zeros(naggs, dtype=np.int32)
        agg_mnl = np.zeros(naggs, dtype=np.int32)
        agg_mxp = np.zeros(naggs, dtype=np.int32)
        agg_mxl = np.zeros(naggs, dtype=np.int32)
        rows_o = _i64()
        amb_o = _i64()
        returned = 0
        outbuf = bytearray()
        feeder = _Blocks(raw, rw, b"", compression,
                         direct_ok=fused is not None)
        try:
            while True:
                blk = feeder.next()
                if blk is None:
                    break
                addr, blen, final = blk
                off = 0
                while off < blen:
                    pad = feeder.view(off)
                    cbuf = _vp(addr + off)
                    if fused is not None:
                        lib.sel_json_agg_fused(
                            cbuf, blen - off, 1 if final else 0,
                            keys_arr, _ptr(key_lens), nk,
                            fused["nleaves"], _ptr(fused["kind"]),
                            _ptr(fused["slot"]), _ptr(fused["op"]),
                            _ptr(fused["isnum"]), _ptr(fused["fn"]),
                            _ptr(fused["fa"]), _ptr(fused["fb"]),
                            _ptr(fused["num"]), _ptr(fused["aoff"]),
                            _ptr(fused["alen"]), fused["blob"],
                            fused["mask"], _ptr(fused["prog"]),
                            fused["prog_len"], _ptr(fused["ecodes"]),
                            _ptr(fused["eops"]), naggs,
                            _ptr(f_aggs["what"]), _ptr(f_aggs["slot"]),
                            _ptr(agg_cnt), _ptr(agg_s), _ptr(agg_mn),
                            _ptr(agg_mx), _ptr(agg_mnp), _ptr(agg_mnl),
                            _ptr(agg_mxp), _ptr(agg_mxl),
                            ctypes.byref(rows_o), ctypes.byref(amb_o),
                            ctypes.byref(consumed))
                        cons = int(consumed.value)
                        stats["bytes_scanned"] += cons
                        if amb_o.value > 0:
                            replay_span(pad, cons)
                        else:
                            results = []
                            for ai, (what, colname, fname) in \
                                    enumerate(aggs):
                                if agg_keys[ai] is None:
                                    results.append(
                                        ("count", int(agg_cnt[ai]), 0.0,
                                         None, None))
                                    continue
                                lo = hi = None
                                if what == 2 and int(agg_mnl[ai]) >= 0:
                                    a0 = int(agg_mnp[ai])
                                    l0 = int(agg_mnl[ai])
                                    lo = _num(bytes(pad[a0:a0 + l0])
                                              .decode())
                                    a1 = int(agg_mxp[ai])
                                    l1 = int(agg_mxl[ai])
                                    hi = _num(bytes(pad[a1:a1 + l1])
                                              .decode())
                                results.append((fname, int(agg_cnt[ai]),
                                                float(agg_s[ai]), lo, hi))
                            _commit_agg(ev, results)
                        off += cons
                        if cons == 0:
                            break
                        continue
                    n = lib.sel_json_scan(
                        cbuf, blen - off, 1 if final else 0, keys_arr,
                        _ptr(key_lens), nk, max_rows, _ptr(starts),
                        _ptr(lens), _ptr(types), _ptr(row_start),
                        _ptr(row_len), ctypes.byref(consumed))
                    if n == 0:
                        break
                    n = int(n)
                    ctx = _Ctx()
                    ctx.buf = cbuf
                    ctx.n = n
                    ctx.starts = [starts[keymap[k], :n]
                                  for k in plan.cols]
                    ctx.lens = [lens[keymap[k], :n] for k in plan.cols]
                    ctx.types = [types[keymap[k], :n]
                                 for k in plan.cols]
                    mask = plan.mask(ctx)
                    ambiguous = plan.amb > 0
                    # bad lines mark EVERY key slot 6 (incl. dummy)
                    bad = types[0, :n] == 6
                    if nk > 1:
                        for ki in range(1, nk):
                            bad = bad & (types[ki, :n] == 6)
                    if bad.any() and not plan.cols and agg_keys.count(
                            None) == len(agg_keys):
                        # COUNT(*)-style: kernels never touch types, so
                        # surface bad lines here
                        ambiguous = True
                    if not ambiguous and aggs is not None:
                        results = []
                        kmask = None
                        if mask is not None:
                            kmask = np.ascontiguousarray(
                                mask.astype(np.uint8))
                        for (what, colname, fname), key in zip(
                                aggs, agg_keys):
                            if key is None:
                                if mask is not None:
                                    results.append(
                                        ("count", int(mask.sum()), 0.0,
                                         None, None))
                                else:
                                    results.append(
                                        ("count", n, 0.0, None, None))
                                continue
                            sl = keymap[key]
                            s = _dbl()
                            mn = _dbl()
                            mx = _dbl()
                            am = _i64()
                            ax = _i64()
                            ab = _i64()
                            cnt = lib.sel_json_agg(
                                cbuf, _ptr(starts[sl, :n]),
                                _ptr(lens[sl, :n]),
                                _ptr(types[sl, :n]), n,
                                _ptr(kmask) if kmask is not None
                                else None, what,
                                ctypes.byref(s), ctypes.byref(mn),
                                ctypes.byref(mx), ctypes.byref(am),
                                ctypes.byref(ax), ctypes.byref(ab))
                            if ab.value > 0:
                                ambiguous = True
                                break
                            lo = hi = None
                            if what == 2 and am.value >= 0:
                                a0 = int(starts[sl, am.value])
                                l0 = int(lens[sl, am.value])
                                lo = _num(bytes(pad[a0:a0 + l0])
                                          .decode())
                                a1 = int(starts[sl, ax.value])
                                l1 = int(lens[sl, ax.value])
                                hi = _num(bytes(pad[a1:a1 + l1])
                                          .decode())
                            results.append((fname, int(cnt),
                                            float(s.value), lo, hi))
                        if not ambiguous:
                            _commit_agg(ev, results)
                    if ambiguous:
                        replay_rows(pad, row_start[:n], row_len[:n],
                                    np.arange(n))
                    stats["bytes_scanned"] += int(consumed.value)
                    off += int(consumed.value)
                    if int(consumed.value) == 0:
                        break
                feeder.advance(off)
                if final:
                    break
            outbuf += out.serialize(ev.aggregate_result())
            returned += len(outbuf)
            yield es.records_message(bytes(outbuf))
            if req.request_progress:
                yield es.progress_message(object_size, object_size,
                                          returned)
            yield es.stats_message(object_size, object_size, returned)
            yield es.end_message()
        except SQLError as e:
            yield es.error_message("InvalidQuery", str(e))

    return gen()


# -------------------------------------------------------------- dispatch


def try_native(req, query: Query, rw, object_size: int,
               out) -> Iterator[bytes] | None:
    """Probe + run the native path.  Returns the event-stream iterator,
    or None (with `rw` rewound) when the pyarrow/row paths must take
    over."""
    if not _enabled() or _load() is None:
        rw.rewind()
        return None
    try:
        if "CSV" in req.input_ser:
            return _try_csv(req, query, rw, object_size, out)
        if "JSON" in req.input_ser:
            return _try_json(req, query, rw, object_size, out)
    except _Fallback:
        pass
    stats["fallback"] += 1
    rw.rewind()
    return None
