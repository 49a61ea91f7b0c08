"""Exact filter-and-aggregate requests over a JSON collection, scanned on
the device.

An :class:`AggregateRequest` is a small generic form of SQL's single-table
aggregate query: a conjunction of inclusive ranges on numeric columns, an
optional group-by on coded (string) columns, and outputs, each the sum or
average of a product of affine terms of columns (``price * (1 - disc) *
(1 + tax)``), or the count of rows.  TPC-H Q1 and Q6 are two instances.

Columns are exact fixed-point integers (``json_store.extract_columns``), so
every factor and product is an integer in the columns' units, and every
sum is exact: the device forms each product in 64-bit integers under a
scoped ``jax.enable_x64`` (the process-wide flag stays off, so other
kernels keep their dtypes), adds up its 8-bit limbs with an int8 matmul
into int32 and recombines them in 64 bits, and a request whose sums could
overflow 64 bits is refused.
A row that lacks a column the request reads is not counted, as if a
predicate on it had failed.

Per group of a sharded warren, :class:`ResidentColumns` holds one
collection's columns on the device for one snapshot key, and
:func:`agg_scan` evaluates every request of a micro-batch in one call,
forming each of the batch's distinct products once per row and returning
per-request, per-group-key partial sums of all of them; :func:`finalize` adds
the groups' partials on the host and forms averages and the SQL order
(ascending group-by values).  Nothing but the columns outlives a batch.
"""

from __future__ import annotations

import dataclasses
import functools
from decimal import Decimal
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import json_store

MAX_RANGES = 4       # range predicates per request
MAX_GROUP_BY = 4     # group-by columns per request
MAX_SUMS = 8         # distinct products summed per request
MAX_FACTORS = 3      # affine factors per product
KEYS_FLOOR = 8       # group-key slots: a power of two, at least this
PRODUCTS_FLOOR = 8   # a batch's product slots: a power of two, at least this
ROWS_FLOOR = 1024    # row slots: a power of two, at least this
COLUMNS_STEP = 8     # column slots: a multiple of this
ROW_BLOCK = 8192     # rows the device scan takes at a time
LIMBS = 8            # 8-bit limbs of a 64-bit product
MAX_ROWS = 1 << 24   # rows a table may hold: 128 a row fills an int32
I32 = json_store.INT32
OPS = ("sum", "avg", "count")


@dataclasses.dataclass(frozen=True)
class Column:
    """A path of the collection's objects: a number kept to ``scale``
    decimals (a date's day number: ``json_store.days_path``, scale 0), or,
    with ``scale`` None, a string resolved to a dense code."""
    path: str
    scale: Optional[int] = 0


@dataclasses.dataclass(frozen=True)
class Range:
    """``lo <= column <= hi`` in the column's fixed-point units; None is
    unbounded."""
    column: Column
    lo: Optional[int] = None
    hi: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Factor:
    """``offset + coef * column``, in the column's units: ``1 - disc`` at
    scale 2 is ``Factor(disc, -1, 100)``."""
    column: Column
    coef: int = 1
    offset: int = 0


Product = Tuple[Factor, ...]


@dataclasses.dataclass(frozen=True)
class Output:
    """``op`` is ``sum`` or ``avg`` of the product of ``factors``, or
    ``count`` (no factors)."""
    name: str
    op: str
    factors: Tuple[Factor, ...] = ()


@dataclasses.dataclass(frozen=True)
class AggregateRequest:
    """One query over ``collection``.  Its answer is a list of rows in
    ascending group-by order, each the group-by strings then the outputs:
    a sum as an exact ``Decimal``, an average as an exact ``Fraction`` (both
    None over no rows), a count as an int.  Without a group-by there is one
    row, as in SQL."""
    collection: str
    where: Tuple[Range, ...] = ()
    group_by: Tuple[Column, ...] = ()
    outputs: Tuple[Output, ...] = ()

    def __post_init__(self):
        if len(self.where) > MAX_RANGES or len(self.group_by) > MAX_GROUP_BY:
            raise ValueError(f"at most {MAX_RANGES} ranges and "
                             f"{MAX_GROUP_BY} group-by columns")
        if any(r.column.scale is None for r in self.where):
            raise ValueError("a range needs a numeric column")
        if any(c.scale is not None for c in self.group_by):
            raise ValueError("a group-by needs a coded column")
        paths: Dict[str, Optional[int]] = {}
        for c in self.columns:
            if paths.setdefault(c.path, c.scale) != c.scale:
                raise ValueError(f"{c.path} at two scales")
        for o in self.outputs:
            if o.op not in OPS or (o.op == "count") != (not o.factors):
                raise ValueError(f"output {o.name}: {o.op} of "
                                 f"{len(o.factors)} factors")
            if len(o.factors) > MAX_FACTORS:
                raise ValueError(f"at most {MAX_FACTORS} factors")
            for f in o.factors:
                if f.column.scale is None:
                    raise ValueError("a factor needs a numeric column")
                if not (I32.min <= f.coef <= I32.max
                        and I32.min <= f.offset <= I32.max):
                    raise ValueError("a factor's coef and offset are 32-bit")
        if len(self.products) > MAX_SUMS:
            raise ValueError(f"at most {MAX_SUMS} distinct products")

    @property
    def columns(self) -> Tuple[Column, ...]:
        """Every column the request reads, each once."""
        cols = [r.column for r in self.where] + list(self.group_by)
        cols += [f.column for o in self.outputs for f in o.factors]
        return tuple(dict.fromkeys(cols))

    @property
    def products(self) -> Tuple[Product, ...]:
        """The distinct products its sums and averages need."""
        return tuple(dict.fromkeys(o.factors for o in self.outputs
                                   if o.op != "count"))


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << max(n - 1, 0).bit_length())


class ResidentColumns:
    """One group's columns of one collection for one snapshot ``key``, on
    the device: int32 values and bool presence, ``[C, N]``, with ``C`` and
    ``N`` rounded up to their buckets (padding rows are absent)."""

    def __init__(self, key, w, collection: str, specs: Sequence[Column]):
        self.key, self.specs = key, tuple(specs)
        self.index = {c: i for i, c in enumerate(self.specs)}
        by_scale: Dict[Optional[int], List[str]] = {}
        for c in self.specs:
            by_scale.setdefault(c.scale, []).append(c.path)
        got = {scale: json_store.extract_columns(
            w, collection, {p: scale for p in paths})
            for scale, paths in by_scale.items()} or \
            {None: json_store.extract_columns(w, collection, {})}
        self.n_rows = len(next(iter(got.values())).rows)
        n_pad = _pow2(self.n_rows, ROWS_FLOOR)
        c_pad = max(1, -(-len(self.specs) // COLUMNS_STEP)) * COLUMNS_STEP
        values = np.zeros((c_pad, n_pad), np.int32)
        present = np.zeros((c_pad, n_pad), bool)
        self.codes: Dict[Column, Tuple[str, ...]] = {}
        self.max_abs: List[int] = []
        for i, c in enumerate(self.specs):
            cols = got[c.scale]
            values[i, :self.n_rows] = cols.values[c.path]
            present[i, :self.n_rows] = cols.present[c.path]
            self.max_abs.append(int(np.abs(values[i].astype(np.int64)).max()))
            if c.scale is None:
                self.codes[c] = cols.codes[c.path]
        self.nbytes = values.nbytes + present.nbytes
        self.values = jax.device_put(values)
        self.present = jax.device_put(present)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.values.shape

    def n_keys(self, request: AggregateRequest) -> int:
        n = 1
        for c in request.group_by:
            n *= max(1, len(self.codes[c]))
        return n


def key_slots(tables: Sequence[ResidentColumns],
              requests: Sequence[AggregateRequest]) -> int:
    """The group-key bucket that fits every request over every table."""
    return _pow2(max(t.n_keys(r) for t in tables for r in requests),
                 KEYS_FLOOR)


def batch_products(requests: Sequence[AggregateRequest]
                   ) -> Tuple[Product, ...]:
    """The distinct products of every request's sums and averages, in
    first-use order: the scan forms each once per row, whichever requests
    share it."""
    return tuple(dict.fromkeys(p for r in requests for p in r.products))


def _width(c_pad: int) -> int:
    return 1 + c_pad + 3 * MAX_RANGES + 2 * MAX_GROUP_BY


def encode(requests: Sequence[AggregateRequest], table: ResidentColumns,
           slots: int, products: Sequence[Product]
           ) -> Tuple[np.ndarray, np.ndarray]:
    """The batch's parameters over one table: every request as one int32
    row of ``[slots, W]`` (the table's row count, the columns it reads,
    its ranges and its group-key strides, each as column slot and
    constants), and ``products`` as int32 ``[P, MAX_FACTORS, 3]`` (each
    factor's column slot, coef and offset; ``P`` bucketed to a power of
    two from ``PRODUCTS_FLOOR``).  Raises OverflowError where a sum could
    leave 64 bits or the table holds more than ``MAX_ROWS`` rows."""
    if table.n_rows > MAX_ROWS:
        raise OverflowError(f"{table.n_rows} rows: a limb sum could "
                            f"leave 32 bits past {MAX_ROWS}")
    c_pad = table.shape[0]
    out = np.zeros((slots, _width(c_pad)), np.int64)
    for qi, r in enumerate(requests):
        row = out[qi]
        row[0] = table.n_rows
        for c in r.columns:
            row[1 + table.index[c]] = 1
        at = 1 + c_pad
        rng = np.tile([0, I32.min, I32.max], MAX_RANGES).reshape(-1, 3)
        for j, p in enumerate(r.where):
            lo = I32.min if p.lo is None else p.lo
            hi = I32.max if p.hi is None else p.hi
            if lo > hi or lo > I32.max or hi < I32.min:
                lo, hi = 1, 0                    # empty
            rng[j] = (table.index[p.column], max(lo, I32.min),
                      min(hi, I32.max))
        row[at:at + 3 * MAX_RANGES] = rng.ravel()
        at += 3 * MAX_RANGES
        stride = 1
        for j, c in enumerate(r.group_by):
            row[at + 2 * j:at + 2 * j + 2] = (table.index[c], stride)
            stride *= max(1, len(table.codes[c]))
    fac = np.zeros((_pow2(len(products), PRODUCTS_FLOOR), MAX_FACTORS, 3),
                   np.int64)
    fac[..., 2] = 1                                 # factors of one
    for j, prod in enumerate(products):
        bound = table.n_rows
        for k, f in enumerate(prod):
            i = table.index[f.column]
            fac[j, k] = (i, f.coef, f.offset)
            bound *= abs(f.offset) + abs(f.coef) * table.max_abs[i]
        if bound >= 1 << 63:
            raise OverflowError(f"a sum of {prod} could leave 64 bits")
    return out.astype(np.int32), fac.astype(np.int32)


def _block(values, present, rows, params, factors, n_keys: int):
    """:func:`agg_scan`'s int32 limb sums over one block of rows, numbered
    ``rows``: ``[Q * n_keys, P * LIMBS + 1]``."""
    c, b = values.shape
    q = params.shape[0]
    need = params[:, 1:1 + c] > 0                               # [Q, C]
    ok = jnp.all(present[None] | ~need[:, :, None], axis=1)     # [Q, B]
    ok &= rows[None] < params[:, :1]                            # padding
    at = 1 + c
    rng = params[:, at:at + 3 * MAX_RANGES].reshape(q, MAX_RANGES, 3)
    v = values[rng[..., 0]]                                     # [Q, R, B]
    ok &= jnp.all((v >= rng[..., 1:2]) & (v <= rng[..., 2:3]), axis=1)
    at += 3 * MAX_RANGES
    grp = params[:, at:at + 2 * MAX_GROUP_BY].reshape(q, MAX_GROUP_BY, 2)
    key = jnp.sum(values[grp[..., 0]] * grp[..., 1:2], axis=1,
                  dtype=jnp.int32)                              # [Q, B]
    keys = jnp.arange(n_keys, dtype=jnp.int32)[None, :, None]
    hit = ok[:, None, :] & (key[:, None, :] == keys)            # [Q, G, B]
    terms = (factors[..., 1:2].astype(jnp.int64)
             * values[factors[..., 0]].astype(jnp.int64)
             + factors[..., 2:3].astype(jnp.int64))             # [P, F, B]
    prod = jnp.prod(terms, axis=1)                              # [P, B]
    # each product's two's complement bytes, less 128 to fit int8: a
    # product is the sum of (limb + 128) * 256^i, modulo 2^64
    words = jnp.stack([prod.astype(jnp.int32),
                       (prod >> 32).astype(jnp.int32)], axis=1)  # [P, 2, B]
    shifts = 8 * jnp.arange(LIMBS // 2, dtype=jnp.int32)[:, None]
    limbs = ((words[:, :, None] >> shifts) & 255) - 128       # [P, 2, 4, B]
    limbs = jnp.concatenate([limbs.reshape(-1, b).astype(jnp.int8),
                             jnp.ones((1, b), jnp.int8)])       # + a count
    return jax.lax.dot_general(
        hit.reshape(q * n_keys, b).astype(jnp.int8), limbs,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_keys",))
def agg_scan(values, present, params, factors, n_keys: int):
    """Every request's partial aggregates over one group's columns.

    ``values`` int32 and ``present`` bool ``[C, N]``; ``params`` int32
    ``[Q, W]`` and ``factors`` int32 ``[P, MAX_FACTORS, 3]`` from
    :func:`encode`.  Returns int64 ``[Q, n_keys, P + 1]``: per request and
    group key, each product's sum over the rows that pass, then their
    count.  Per block of at most ``ROW_BLOCK`` rows, the products are
    formed in int64 and split into 8-bit limbs, and one int8 matmul of the
    0/1 hit matrix by the limbs adds them up in int32 (exact, as a limb
    sum is at most 128 a row and a table at most ``MAX_ROWS`` rows); the
    limb sums are recombined in wrapping int64, exact as :func:`encode`
    keeps every sum within 64 bits.  Call under ``jax.enable_x64(True)``."""
    c, n = values.shape
    q, p = params.shape[0], factors.shape[0]
    blk = min(n, ROW_BLOCK)

    def step(i, acc):
        at = i * blk
        return acc + _block(
            jax.lax.dynamic_slice_in_dim(values, at, blk, axis=1),
            jax.lax.dynamic_slice_in_dim(present, at, blk, axis=1),
            at + jnp.arange(blk, dtype=jnp.int32), params, factors, n_keys)

    acc = jax.lax.fori_loop(0, n // blk, step, jnp.zeros(
        (q * n_keys, p * LIMBS + 1), jnp.int32))
    count = acc[:, -1:].astype(jnp.int64)
    limbs = acc[:, :-1].reshape(-1, p, LIMBS).astype(jnp.int64) \
        + 128 * count[:, :, None]
    sums = jnp.sum(limbs << (8 * jnp.arange(LIMBS, dtype=jnp.int64)),
                   axis=-1)
    return jnp.concatenate([sums, count], axis=1).reshape(q, n_keys, p + 1)


def scan(table: ResidentColumns, params: np.ndarray, factors: np.ndarray,
         n_keys: int):
    """Launch :func:`agg_scan` on ``table``; returns the device result."""
    with jax.enable_x64(True):
        return agg_scan(table.values, table.present, params, factors,
                        n_keys=n_keys)


def fetch(results: list) -> List[np.ndarray]:
    """Every launched result, copied back in one call."""
    with jax.enable_x64(True):
        return jax.device_get(results)


def finalize(request: AggregateRequest,
             partials: Sequence[Tuple[ResidentColumns, np.ndarray]],
             slot: int, products: Sequence[Product]) -> List[tuple]:
    """The answer: the groups' partial sums of request ``slot`` (each
    ``[n_keys, P + 1]``, over the batch's ``products``) added exactly,
    keyed by group-by strings, averages formed, rows in ascending group-by
    order."""
    cols = [products.index(p) for p in request.products]
    acc: Dict[tuple, List[int]] = {}
    for table, part in partials:
        cards = [max(1, len(table.codes[c])) for c in request.group_by]
        for g in range(table.n_keys(request)):
            row = part[slot, g]
            if row[-1] == 0:
                continue
            key, rest = [], g
            for c, card in zip(request.group_by, cards):
                key.append(table.codes[c][rest % card])
                rest //= card
            tot = acc.setdefault(tuple(key), [0] * (len(products) + 1))
            for col in cols:
                tot[col] += int(row[col])
            tot[-1] += int(row[-1])
    if not request.group_by and not acc:
        acc[()] = [0] * (len(products) + 1)
    rows = []
    for key in sorted(acc):
        tot, count = acc[key], acc[key][-1]
        out = list(key)
        for o in request.outputs:
            if o.op == "count":
                out.append(count)
                continue
            s = tot[products.index(o.factors)]
            scale = sum(f.column.scale for f in o.factors)
            if count == 0:
                out.append(None)
            elif o.op == "sum":
                out.append(Decimal(f"{s}E-{scale}"))
            else:
                out.append(Fraction(s, 10 ** scale * count))
        rows.append(tuple(out))
    return rows
