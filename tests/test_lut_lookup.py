"""How a lookup table is applied to a column of codes.

`engine/eval._lookup` has one algorithm in three formulations: in a program
traced for the TPU a table of at most `LUT_SELECT_MAX` entries of at most
32 bits is a chain of compare-selects, one of at most `LUT_BLOCKED_MAX` is
that chain a block of the table at a time under one loop, and elsewhere and
for longer or wider tables it is a gather.  All are bit-equal for every
dtype a LUT has; the choice follows the dispatch platform and the table's
static length and width, as the lowered text of a by-status chain shows; a
served query's chain span says which form its program holds."""
from __future__ import annotations

import os
import re

import jax
import numpy as np
import pytest

from pixie_tpu.compiler import compile_pxl
from pixie_tpu.engine import eval as ev
from pixie_tpu.engine import execute_plan
from pixie_tpu.engine import executor as ex
from pixie_tpu.metadata import state as mdstate
from pixie_tpu.ops import groupby
from pixie_tpu.plan.plan import AggOp, Call, Column, Literal
from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT
from pixie_tpu.types import Relation, UInt128
from pixie_tpu.udf import registry
from tests.test_trace_layers import _agent_spans, _chains, fresh_ring, serving  # noqa: F401

BY_STATUS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "scripts", "http_by_status.pxl")

DTYPES = {"int32": np.int32, "bool": np.bool_, "float32": np.float32,
          "int64": np.int64, "float64": np.float64}


def _trace_for(monkeypatch, backend: str) -> None:
    """Trace as a process whose kernels go to `backend` does: the formulation
    follows the dispatch platform, which is all of the TPU a CPU run can
    stand in for."""
    monkeypatch.setattr(groupby, "dispatch_backend", lambda: backend)


def _lut(dtype: str, k: int) -> np.ndarray:
    rng = np.random.default_rng(k)
    if dtype == "bool":
        return rng.integers(0, 2, k).astype(np.bool_)
    if dtype.startswith("float"):
        ft = np.finfo(DTYPES[dtype])
        out = (rng.standard_normal(k) * (ft.max / 8)).astype(ft.dtype)
        # values a sum or a GEMM would not carry through; a select does
        odd = [np.nan, -0.0, np.inf, ft.smallest_subnormal, -np.inf]
        out[: min(k, 5)] = odd[: min(k, 5)]
        return out
    info = np.iinfo(DTYPES[dtype])
    return rng.integers(info.min, info.max, k, dtype=DTYPES[dtype])


def _codes(k: int) -> np.ndarray:
    """Null codes, both ends of the table, codes beyond it, and the rest."""
    rng = np.random.default_rng(k + 1)
    return np.concatenate([
        np.asarray([-1, 0, max(k - 1, 0), k, k + 7, -1], np.int32),
        rng.integers(-1, max(k, 1), 250, dtype=np.int32)])


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    if a.dtype.kind == "f":
        return a.view({8: np.int64, 4: np.int32}[a.dtype.itemsize])
    return a


def _gathers(text: str) -> int:
    return text.count('"stablehlo.gather"(')


def _whiles(text: str) -> int:
    return text.count("stablehlo.while(")


def _md_whiles(text: str) -> int:
    """The loops that `_lookup` emitted, in a text lowered with debug info
    (a chain has others: the group-by's chunk loops)."""
    return len(re.findall(r'^#loc\d+ = loc\("[^"]*px\.md_lookup/while"',
                          text, re.M))


def _md_gathers(text: str) -> int:
    """The gathers that `_lookup` emitted, in a text lowered with debug
    info (a chain has others: `encode_against`'s searchsorted)."""
    defs = dict(re.findall(r'^(#loc\d+) = (.*)$', text, re.M))

    def from_lookup(ref: str, depth: int = 0) -> bool:
        d = defs.get(ref, "")
        return '"_lookup"' in d or (depth < 8 and any(
            from_lookup(r, depth + 1) for r in re.findall(r'#loc\d+', d)))

    refs = re.findall(r'"stablehlo\.gather"\(.*loc\((#loc\d+)\)$', text, re.M)
    assert len(refs) == _gathers(text)
    return sum(from_lookup(r) for r in refs)


def _apply(lut, codes, fill):
    """The lowered text of `apply_lut` over `codes`, and its answer."""
    fn = jax.jit(lambda lt, c: ev.apply_lut(lt, c, fill))
    return fn.lower(lut, codes).as_text(), fn(lut, codes)


# ------------------------------------------------------------- bit-equality


WIDE = {"int64", "float64"}
NARROW = sorted(set(DTYPES) - WIDE)


@pytest.mark.parametrize("k", [1, 2, 110, ev.LUT_SELECT_MAX])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_select_is_bit_equal_to_the_gather(dtype, k, monkeypatch):
    """The chain against `jnp.take` for every dtype; through `apply_lut`,
    traced for the TPU, the narrow dtypes take it, unlooped, and the wide
    ones keep the gather."""
    lut, codes = _lut(dtype, k), _codes(k)
    safe = np.clip(codes, 0, k - 1)
    chained = jax.jit(ev._select_chain)(lut, safe)
    assert chained.dtype == lut.dtype
    np.testing.assert_array_equal(_bits(chained), _bits(lut[safe]))
    np.testing.assert_array_equal(_bits(chained), _bits(jax.jit(
        lambda lt, i: jax.numpy.take(lt, i))(lut, safe)))
    fill = False if dtype == "bool" else -1
    text, gathered = _apply(lut, codes, fill)
    assert _gathers(text) == 1
    _trace_for(monkeypatch, "tpu")
    text, selected = _apply(lut, codes, fill)
    assert _gathers(text) == (dtype in WIDE) and _whiles(text) == 0
    assert selected.dtype == gathered.dtype == lut.dtype
    np.testing.assert_array_equal(_bits(selected), _bits(gathered))
    want = np.where(codes >= 0, lut[safe], np.asarray(fill, lut.dtype))
    np.testing.assert_array_equal(_bits(selected), _bits(want))


@pytest.mark.parametrize("k", [ev.LUT_SELECT_MAX + 1, 440, 512, 1000,
                               ev.LUT_BLOCKED_MAX])
@pytest.mark.parametrize("dtype", NARROW)
def test_blocked_is_bit_equal_to_the_gather(dtype, k, monkeypatch):
    """Blocks of compare-selects under one loop against `jnp.take`; through
    `apply_lut`, traced for the TPU, one loop and no gather, answering as
    the gather does."""
    lut, codes = _lut(dtype, k), _codes(k)
    safe = np.clip(codes, 0, k - 1)
    blocked = jax.jit(ev._blocked_select)(lut, safe)
    assert blocked.dtype == lut.dtype
    np.testing.assert_array_equal(_bits(blocked), _bits(lut[safe]))
    fill = False if dtype == "bool" else -1
    text, gathered = _apply(lut, codes, fill)
    assert _gathers(text) == 1 and _whiles(text) == 0
    _trace_for(monkeypatch, "tpu")
    text, selected = _apply(lut, codes, fill)
    assert _gathers(text) == 0 and _whiles(text) == 1
    assert selected.dtype == gathered.dtype == lut.dtype
    np.testing.assert_array_equal(_bits(selected), _bits(gathered))
    want = np.where(codes >= 0, lut[safe], np.asarray(fill, lut.dtype))
    np.testing.assert_array_equal(_bits(selected), _bits(want))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_a_longer_table_keeps_the_gather(dtype, monkeypatch):
    _trace_for(monkeypatch, "tpu")
    k = ev.LUT_BLOCKED_MAX + 1
    lut, codes = _lut(dtype, k), _codes(k)
    text, out = _apply(lut, codes, 0)
    assert _gathers(text) == 1 and _whiles(text) == 0
    want = np.where(codes >= 0, lut[np.clip(codes, 0, k - 1)],
                    np.asarray(0, lut.dtype))
    np.testing.assert_array_equal(_bits(out), _bits(want))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_an_empty_table_is_all_fill(dtype, backend, monkeypatch):
    _trace_for(monkeypatch, backend)
    fill = True if dtype == "bool" else 7
    text, out = _apply(np.empty(0, DTYPES[dtype]), _codes(0), fill)
    assert _gathers(text) == _whiles(text) == 0
    assert out.dtype == DTYPES[dtype]
    assert np.asarray(out).tolist() == [fill] * len(_codes(0))


def test_the_form_follows_platform_length_and_width(monkeypatch):
    # this process dispatches to XLA-CPU
    assert ev.lut_form(110, 4) == ev.lut_form(440, 4) == "gather"
    _trace_for(monkeypatch, "tpu")
    assert ev.lut_form(0, 4) is None
    assert ev.lut_form(1, 1) == ev.lut_form(ev.LUT_SELECT_MAX, 4) == "select"
    assert ev.lut_form(ev.LUT_SELECT_MAX + 1, 4) == "blocked"
    assert ev.lut_form(ev.LUT_BLOCKED_MAX, 1) == "blocked"
    assert ev.lut_form(ev.LUT_BLOCKED_MAX + 1, 4) == "gather"
    for k in (110, 440, ev.LUT_BLOCKED_MAX + 1):
        assert ev.lut_form(k, 8) == "gather"
    assert ev.LUT_SELECT_MAX < ev.LUT_BLOCKED_MAX <= 4096


# ------------------------------------------------- through the ExprCompiler


def _compile(expr, dtypes, dicts=None):
    ec = ev.ExprCompiler(dtypes, dicts or {}, registry)
    sval = ec.compile(expr)
    luts = dict(ec.luts)

    def run(cols):
        fn = jax.jit(lambda c, lt: sval.build({"cols": c, "luts": lt}))
        text = fn.lower(cols, luts).as_text()
        return _gathers(text), _whiles(text), np.asarray(fn(cols, luts))
    return ec, sval, run


#: (function, its domain, values in and out of it)
INT_DOMAIN = {
    "http_resp_message": np.asarray(
        [99, 100, 200, 404, 418, 500, 599, 600, -5, 2**40], np.int64),
    "protocol_name": np.asarray([-1, 0, 1, 5, 12, 13, 10**12], np.int64),
}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("fn", sorted(INT_DOMAIN))
def test_int_domain_lookup_in_and_out_of_domain(fn, backend, monkeypatch):
    _trace_for(monkeypatch, backend)
    x = INT_DOMAIN[fn]
    ec, sval, run = _compile(Call(fn, (Column("x"),)), {"x": DT.INT64})
    gathers, whiles, codes = run({"x": x})
    # http_resp_message's 500 entries are over the unlooped chain's
    # constant and under the loop's, protocol_name's 13 under both
    (k,) = [len(lut) for lut in ec.luts.values()]
    assert (k > ev.LUT_SELECT_MAX) == (fn == "http_resp_message")
    assert k <= ev.LUT_BLOCKED_MAX
    form = ("gather" if backend == "cpu" else
            "select" if k <= ev.LUT_SELECT_MAX else "blocked")
    assert gathers == (form == "gather") and whiles == (form == "blocked")
    host = registry.scalar(fn, (DT.INT64,)).fn
    assert sval.dictionary.decode(codes) == [host(int(v)) for v in x]
    assert ec.lut_forms() == {f"lut_{f}": int(f == form)
                              for f in ("select", "blocked", "gather")}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("out", ["string", "int", "bool", "pair"])
def test_dictionary_udfs_agree_across_forms(out, backend, monkeypatch):
    """Scalar string UDFs of each result type, and the pair LUT over two
    dictionary columns, against the host function on every value."""
    from pixie_tpu.table.dictionary import Dictionary

    _trace_for(monkeypatch, backend)
    words = [f"w{i}/{'x' * (i % 7)}" for i in range(37)]
    da, db = Dictionary(words), Dictionary(["x", "w1", "/"])
    rng = np.random.default_rng(3)
    a = rng.integers(-1, len(words), 300).astype(np.int32)
    b = rng.integers(-1, 3, 300).astype(np.int32)
    expr, host = {
        "string": (Call("toupper", (Column("a"),)), lambda v, _w: v.upper()),
        "int": (Call("length", (Column("a"),)), lambda v, _w: len(v)),
        "bool": (Call("contains", (Column("a"), Literal("xx", DT.STRING))),
                 lambda v, _w: "xx" in v),
        "pair": (Call("contains", (Column("a"), Column("b"))),
                 lambda v, w: w in v),
    }[out]
    ec, sval, run = _compile(expr, {"a": DT.STRING, "b": DT.STRING},
                             {"a": da, "b": db})
    gathers, _whiles, got = run({"a": a, "b": b})
    # `length` gives an INT64 LUT, which keeps the gather on either platform
    assert gathers == (backend == "cpu" or out == "int")
    if sval.dictionary is not None:
        got = sval.dictionary.decode(got)
    null = {"string": None, "int": 0, "bool": False, "pair": False}[out]
    want = [host(words[i], db.values()[j]) if i >= 0 and (
        out != "pair" or j >= 0) else null for i, j in zip(a, b)]
    assert list(got) == want
    assert ec.lut_forms()["lut_gather"] == gathers
    assert sum(ec.lut_forms().values()) == 1


# ----------------------------------------------- the by-status chain's text


def _by_status_store(k: int, rows: int = 4096):
    """http_events over `k` processes, one pod each, eight services: the
    store, the node's metadata, and each row's process and status."""
    upids = [UInt128.make_upid(1, 1000 + i, 5 + i) for i in range(k)]
    ups = []
    for i, u in enumerate(upids):
        ups += [{"kind": "pod", "uid": f"p{i}", "name": f"pod-{i}",
                 "namespace": "d", "node": "n", "ip": "10.0.0.1",
                 "phase": "Running", "create_time_ns": 1},
                {"kind": "process", "upid": u, "pod_uid": f"p{i}",
                 "container_id": ""}]
    for j in range(8):
        ups.append({"kind": "service", "uid": f"s{j}", "name": f"svc-{j}",
                    "namespace": "d", "cluster_ip": "10.96.0.1",
                    "pod_uids": [f"p{i}" for i in range(j, k, 8)]})
    m = mdstate.MetadataStateManager(asid=1, node_name="n")
    m.apply_updates(ups)
    ts = TableStore()
    t = ts.create("http_events", Relation.of(
        ("time_", DT.TIME64NS), ("upid", DT.UINT128),
        ("resp_status", DT.INT64), ("latency", DT.INT64)))
    rng = np.random.default_rng(k)
    who = np.concatenate([np.arange(k), rng.integers(0, k, rows)])
    status = rng.choice([200, 404, 500], len(who))
    t.write({"time_": np.arange(len(who), dtype=np.int64) + 10**9,
             "upid": [upids[i] for i in who], "resp_status": status,
             "latency": rng.integers(1, 10**6, len(who))})
    return ts, m, who, status


@pytest.fixture
def metadata():
    old = mdstate.global_manager()
    yield mdstate.set_global_manager
    mdstate.set_global_manager(old)


def _by_status_plan(ts):
    with open(BY_STATUS) as f:
        src = "import px\n" + f.read().replace("__START_TIME__", "0")
    return src, compile_pxl(src, ts.schemas()).plan


def _by_status_step(ts) -> tuple[str, dict]:
    """The lowered text of the by-status chain's agg step, and the LUT
    forms its span would carry, traced where the caller stands."""
    _src, plan = _by_status_plan(ts)
    (agg,) = [op for op in plan.ops() if isinstance(op, AggOp)]
    ex._KERNEL_CACHE.clear()  # a kernel traced for the other platform
    s = ex.PlanExecutor(plan, ts, mesh=None)._agg_setup(agg)
    n = 8192
    cols = {name: np.zeros(n, np.int32 if s.dtypes[name] == DT.UINT128
                           else np.int64) for name in s.names}
    state = {name: uda.init(s.num_groups, in_dt)
             for name, uda, in_dt in s.init_specs}
    lowered = s.step.lower(cols, np.int64(n), np.int64(0), np.int64(2**62),
                           s.kern.init_limits(), s.kern.luts, state)
    return lowered.as_text(debug_info=True), s.kern.lut_forms()


def _forms(select=0, blocked=0, gather=0) -> dict:
    return {"lut_select": select, "lut_blocked": blocked, "lut_gather": gather}


@pytest.mark.parametrize("backend,k,forms", [
    ("tpu", 110, _forms(select=1)),
    ("tpu", ev.LUT_SELECT_MAX + 1, _forms(blocked=1)),
    ("tpu", ev.LUT_BLOCKED_MAX + 1, _forms(gather=1)),
    (None, 110, _forms(gather=1)),
    (None, ev.LUT_SELECT_MAX + 1, _forms(gather=1)),
])
def test_by_status_chain_gathers_only_where_it_should(
        backend, k, forms, metadata, monkeypatch):
    ts, m, _who, _status = _by_status_store(k, rows=64)
    metadata(m)
    if backend:
        _trace_for(monkeypatch, backend)
    text, got = _by_status_step(ts)
    assert "/px.md_lookup/" in text
    assert got == forms
    assert _md_gathers(text) == forms["lut_gather"]
    assert _md_whiles(text) == forms["lut_blocked"]


@pytest.mark.parametrize("form", ["select", "blocked"])
def test_by_status_answers_equal_across_forms(form, metadata, monkeypatch):
    """The same plan run with its LUT as a gather and as compare-selects,
    unlooped or in blocks (all on XLA-CPU, the second traced as for the
    TPU) answers alike, and its counts are the rows of each service that
    are not 404s."""
    ts, m, who, status = _by_status_store(110)
    metadata(m)
    _src, plan = _by_status_plan(ts)

    def answer():
        ex._KERNEL_CACHE.clear()
        df = execute_plan(plan, ts)["out"].to_pandas()
        return df.sort_values(["service", "resp_status"]).reset_index(drop=True)

    gathered = answer()
    monkeypatch.setattr(ev, "lut_form", lambda k, size: (
        form if k and size <= 4 else "gather"))
    selected = answer()
    ex._KERNEL_CACHE.clear()
    assert gathered.equals(selected)
    want = {f"d/svc-{j}": int(((who % 8 == j) & (status != 404)).sum())
            for j in range(8)}
    assert gathered.groupby("service")["cnt"].sum().to_dict() == want


# ------------------------------------------------------ the span's counters


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_served_by_status_chain_span_says_how_its_lut_is_applied(
        backend, metadata, monkeypatch, fresh_ring):
    """A served `http_by_status` query's chain span carries `lut_select`,
    `lut_blocked` and `lut_gather` beside engine, arm and source: the one
    metadata LUT, in the form the dispatch platform gives it."""
    ts, m, _who, _status = _by_status_store(110)
    metadata(m)
    _trace_for(monkeypatch, backend)
    src, _plan = _by_status_plan(ts)
    with serving(ts, monkeypatch) as client:
        out = client.execute_script(src)["out"].to_pandas()
    assert len(set(out["service"])) == 8 and out["cnt"].sum() > 0
    (chain,) = _chains(_agent_spans())
    a = chain.attributes
    assert a["engine"] == "xla_cpu_chain" and a["source"] == "cold"
    assert (a["lut_select"], a["lut_blocked"], a["lut_gather"]) == (
        (1, 0, 0) if backend == "tpu" else (0, 0, 1))
