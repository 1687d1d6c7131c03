"""Constants kept on the device across ``CompiledArtifact.run`` calls.

A read-only constant is uploaded once and reused while the same object
is bound (``hit``); another read-only array replaces it (``upload``); a
writeable one, or a read-only view of a writeable base, is uploaded for
its call alone (``bypass``).  Every executable meets its constants as
device arrays of one form, so a served batch after the open-loop
benchmark's warm-up compiles nothing."""
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.frontends import zoo
from repro.instrument import MetricsRegistry, use_metrics
from repro.kernels import ops
from repro.passes import interp
from repro.serve import ServeConfig, ServeEngine

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _int8(shape, rng):
    return rng.integers(-4, 5, size=shape, dtype=np.int8)


def _writeable(src, seed=0):
    rng = np.random.default_rng(seed)
    return {k: _int8(v.shape, rng) for k, v in src.values.items()
            if v.is_constant}


def _read_only(src, seed=0):
    """Host copies of device arrays, as the benchmark makes its
    weights: read-only, over a read-only base."""
    return {k: np.asarray(jnp.asarray(v))
            for k, v in _writeable(src, seed).items()}


def _read_only_views(src, seed=0):
    """Read-only views whose base stays writeable."""
    out = {}
    for k, v in _writeable(src, seed).items():
        view = v.view()
        view.flags.writeable = False
        out[k] = view
    return out


@pytest.fixture
def lenet():
    """A fresh artifact (its own table); the executables are shared
    through the exec cache, so only the first build compiles."""
    return api.compile_graph(zoo.lenet5())


def _inputs(src, batch, seed=1):
    rng = np.random.default_rng(seed)
    (name,) = src.graph_inputs
    shape = src.values[name].shape
    return _int8(shape if batch is None else (batch,) + shape, rng)


def _reference(src, params, x, batch):
    (name,) = src.graph_inputs
    (out,) = src.graph_outputs
    if batch is None:
        return np.asarray(interp.graph_outputs(src, {**params, name: x})[out])
    return np.stack([
        np.asarray(interp.graph_outputs(src, {**params, name: x[i]})[out])
        for i in range(batch)])


def _outcomes(reg):
    c = reg.counter("run_const_resident_total", labels=("outcome",))
    return {o: int(c.value(outcome=o)) for o in ("hit", "upload", "bypass")}


def _const_bytes(reg):
    return int(reg.counter("run_h2d_bytes_total",
                           labels=("kind",)).value(kind="constants"))


# ``per_call`` are the outcomes of each of three calls with the same
# objects bound (``fresh_read_only`` makes new ones for every call)
OUTCOMES = {
    "read_only": [("upload", True), ("hit", False), ("hit", False)],
    "fresh_read_only": [("upload", True)] * 3,
    "writeable": [("bypass", True)] * 3,
    "read_only_view": [("bypass", True)] * 3,
}
MAKERS = {"read_only": _read_only, "fresh_read_only": _read_only,
          "writeable": _writeable, "read_only_view": _read_only_views}


@pytest.mark.parametrize("batch", [None, 3], ids=["per_sample", "batched"])
@pytest.mark.parametrize("kind", sorted(OUTCOMES))
def test_outcomes_over_repeated_calls(lenet, kind, batch):
    src = lenet.source
    n_const = sum(v.is_constant for v in src.values.values())
    x = _inputs(src, batch)
    params = MAKERS[kind](src)
    total = sum(v.nbytes for v in params.values())
    want = _reference(src, params, x, batch)
    for i, (outcome, moves) in enumerate(OUTCOMES[kind]):
        if kind == "fresh_read_only" and i:
            params = {k: np.asarray(jnp.asarray(v))
                      for k, v in params.items()}
        reg = MetricsRegistry()
        with use_metrics(reg):
            got = lenet.run(x, params)
        expect = {"hit": 0, "upload": 0, "bypass": 0, outcome: n_const}
        assert _outcomes(reg) == expect, i
        assert lenet.last_run_stats["constants"] == expect, i
        assert _const_bytes(reg) == (total if moves else 0), i
        np.testing.assert_array_equal(np.asarray(got), want)
    kept = 0 if OUTCOMES[kind][0][0] == "bypass" else n_const
    assert len(lenet._resident) == kept


@pytest.mark.parametrize("batch", [None, 2], ids=["per_sample", "batched"])
def test_writeable_weight_changed_in_place_gives_new_answer(lenet, batch):
    src = lenet.source
    x = _inputs(src, batch)
    params = _writeable(src)
    first = np.asarray(lenet.run(x, params))
    np.testing.assert_array_equal(first, _reference(src, params, x, batch))
    name = sorted(params)[-1]
    params[name] *= -1
    second = np.asarray(lenet.run(x, params))
    np.testing.assert_array_equal(second, _reference(src, params, x, batch))
    assert not np.array_equal(first, second)


def test_read_only_view_of_writeable_base_follows_the_base(lenet):
    """The caller can still change the base a read-only view shows, so
    the view is never kept: the next call sees the new values."""
    src = lenet.source
    x = _inputs(src, 2)
    base = _writeable(src)
    views = {}
    for k, v in base.items():
        views[k] = v.view()
        views[k].flags.writeable = False
    first = np.asarray(lenet.run(x, views))
    name = sorted(base)[-1]
    base[name] *= -1
    second = np.asarray(lenet.run(x, views))
    np.testing.assert_array_equal(second, _reference(src, base, x, 2))
    assert not np.array_equal(first, second)
    assert lenet.last_run_stats["constants"]["bypass"] == len(base)


@pytest.mark.parametrize("bind_inputs", [False, True],
                         ids=["nothing_bound", "inputs_bound"])
def test_random_fill_keeps_one_entry_per_constant(bind_inputs):
    """Every call of the random-fill path binds fresh device arrays:
    each replaces its constant's entry, so the table never grows."""
    from repro.core import cnn_graphs

    art = api.compile_graph(cnn_graphs.conv_relu(8))
    src = art.source
    consts = {k for k, v in src.values.items() if v.is_constant}
    x = _inputs(src, None) if bind_inputs else None
    for seed in range(20):
        art.run(x, seed=seed)
        assert art.last_run_stats["constants"]["upload"] == len(consts)
        assert set(art._resident._entries) == consts
    assert len(art._resident) == len(consts)


@pytest.mark.parametrize("batch", [None, 4], ids=["per_sample", "batched"])
def test_int8_lenet_bit_identical_to_uploading_every_call(lenet, batch):
    """An upload, then hits, against the path that hands the host
    constants over on every call (``resident=None``) and against the
    DFG interpreter."""
    src = lenet.source
    (name,) = src.graph_inputs
    (out,) = src.graph_outputs
    x = _inputs(src, batch, seed=7)
    params = _read_only(src, seed=3)
    env = {**params, name: x}
    if batch is None:
        plain = np.asarray(ops.run_compiled(lenet.design, env)[out])
    else:
        plain = np.asarray(ops.run_compiled_batched(lenet.design, env,
                                                    batch)[out])
    want = _reference(src, params, x, batch)
    np.testing.assert_array_equal(plain, want)
    for _ in range(3):
        got = np.asarray(lenet.run(x, params))
        assert got.dtype == plain.dtype
        np.testing.assert_array_equal(got, plain)
    assert lenet.last_run_stats["constants"]["hit"] == len(params)


@pytest.mark.parametrize("shared", [True, False],
                         ids=["same_params", "own_params"])
def test_two_threads_share_one_artifact(lenet, shared):
    """Two threads run one artifact at once; with their own read-only
    params they replace each other's entries on every call."""
    src = lenet.source
    params = [_read_only(src, seed=5)] * 2 if shared else \
        [_read_only(src, seed=5), _read_only(src, seed=6)]
    xs = [_inputs(src, 3, seed=s) for s in (8, 9)]
    wants = [_reference(src, p, x, 3) for p, x in zip(params, xs)]
    lenet.run(xs[0], params[0])  # compile outside the race
    errors = []
    start = threading.Barrier(2)

    def work(i):
        try:
            start.wait()
            for _ in range(6):
                got = np.asarray(lenet.run(xs[i], params[i]))
                np.testing.assert_array_equal(got, wants[i])
        except BaseException as exc:  # surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(i,)) for i in (0, 1)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # switch threads inside the table's code
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(lenet._resident) == len(params[0])


@pytest.mark.parametrize("make", [_read_only, _writeable],
                         ids=["read_only", "writeable"])
def test_served_batches_after_open_loop_warmup_compile_nothing(make):
    """The open-loop benchmark's order: every batch extent 1..32 run
    on the main thread with host params and no registry, then the same
    params behind a started ``ServeEngine``, whose worker thread runs
    under the engine's registry.  No served batch may compile or miss
    the exec cache, and no constant is uploaded again."""
    art = api.compile_graph(zoo.lenet5())
    src = art.source
    params = make(src, seed=11)
    max_batch = 32
    pool = _inputs(src, max_batch, seed=12)
    for n in range(1, max_batch + 1):
        art.run(pool[:n], params)

    compiles = []

    def on_duration(event, duration, **_kw):
        if event == BACKEND_COMPILE:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    misses = ops.exec_cache_stats["misses"]
    try:
        cfg = ServeConfig(max_batch=max_batch, latency_budget_ms=100)
        with ServeEngine(art, cfg, params=params) as eng:
            for n in range(1, max_batch + 1):
                futs = [eng.submit(pool[i]) for i in range(n)]
                got = np.stack([f.result(timeout=60) for f in futs])
                np.testing.assert_array_equal(
                    got, _reference(src, params, pool[:n], n))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert ops.exec_cache_stats["misses"] == misses
    sizes = {r["n"] for r in eng.flight_records()}
    served = eng.metrics()["counters"]["run_const_resident_total"]["values"]
    outcomes = {r["labels"]["outcome"]: r["value"] for r in served}
    assert outcomes.get("upload", 0) == 0
    n_const = len(params)
    if make is _read_only:
        assert outcomes == {"hit": n_const * eng.stats["batches"]}
    else:
        assert outcomes == {"bypass": n_const * eng.stats["batches"]}
    assert sizes == set(range(1, max_batch + 1))
