#!/usr/bin/env python3
"""Bring-up check of the MING path on one TPU chip.

    python3 chip_smoke.py

Runs, in this one process and through the calls a user makes,
``frontends.import_model`` → ``repro.compile_graph`` →
``CompiledArtifact.run`` → ``ServeEngine``, and checks every answer
against the DFG interpreter (``passes.interp``) run on the host CPU —
the reference that shares no lowering with the code under test.
Phases, one line each (wall seconds, XLA compile seconds, what was
checked):

* ``device``: JAX's default device is a TPU.  Anything else exits 1;
  there is no CPU or interpret-mode fallback.
* ``served_lenet5``: the LeNet-5 ONNX golden at its published width
  (32×32×1, C6/C16, 120/84/10) behind a ``ServeEngine``; 48 int8
  requests in bursts that form batches of 1, a middle size and
  ``max_batch``.  Every future resolves, nothing is rejected, every
  batch's outcome is ``ok``, every answer is bit-exact.
* ``per_sample_lenet5``: one unbatched ``art.run``, bit-exact; the
  executable it ran holds the Pallas streaming kernel
  (``tpu_custom_call``) for conv 1, which is fed int8.  Conv 2 is fed
  conv 1's int32 accumulators and lowers through XLA.
* ``resnet_tiny``: the strided ONNX golden (stride 2, folded
  BatchNorm, global average pool), served and per sample, bit-exact.
  Only its per-sample stem conv, fed int8, runs in the Pallas kernel:
  convs 2 and 3 take int32 accumulators, and the served path lowers
  every integer conv through XLA.
* ``deep_cascade_224``: 4 (Conv3×3 + ReLU) layers of 136 channels at
  224², partitioned into 4 groups for KV260, float32, per sample and
  batched at 4.  The executable of every group holds a Pallas kernel;
  outputs agree with the reference within ``F32_REL_BOUND``.
* ``resnet18_strided``: the four stride-2 convs of ResNet-18 at their
  published widths (7×7 stem at 224²×3→64, then 3×3 at 56²×64→128,
  28²×128→256, 14²×256→512), each + ReLU, a 2×2 max pool after the
  stem; float32, per sample and batched at 4, checked like
  ``deep_cascade_224``.  Every strided conv here runs in the Pallas
  kernel, the last one weight-streamed in 4 tiles.

Any failure raises and exits non-zero.  When every phase passed, the
last line is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}``.  Inputs and weights are drawn from ``SEED``; the
models are files and builders in this repository.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
LENET5 = os.path.join(REPO, "tests", "golden", "lenet5.onnx")
RESNET_TINY = os.path.join(REPO, "tests", "golden", "resnet_tiny.onnx")
SEED = 0
#: bound on both the relative L2 error and max|err| / max|reference| of
#: the float32 model.  Rounding every conv operand to bfloat16 (one MXU
#: pass) gives 4.4e-3 on deep_cascade_224 in a host emulation; a
#: float32-exact matmul gives ~1e-6.
F32_REL_BOUND = 1e-2
#: how long one request or one run may take, compiles included
RESULT_TIMEOUT_S = 600
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A phase saw a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) so each phase can report its compile seconds."""

    def __init__(self) -> None:
        self.secs = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.secs += duration


def reference(dfg, params, xs):
    """``interp.graph_outputs`` of ``dfg`` for each sample of ``xs``,
    computed on the host CPU."""
    import jax

    from repro.passes import interp

    (out_name,) = dfg.graph_outputs
    (in_name,) = dfg.graph_inputs
    with jax.default_device(jax.devices("cpu")[0]):
        fn = jax.jit(lambda env: interp.graph_outputs(dfg, env)[out_name])
        return [np.asarray(fn({**params, in_name: x})) for x in xs]


def executed_texts(art, params, x, batch=None) -> list[str]:
    """StableHLO of each group's executable that ``art.run`` cached and
    ran for ``params`` and samples like ``x``, unbatched or at
    ``batch``.  Each group's argument types are the previous groups'
    output types, as the run chained them."""
    import jax

    from repro.kernels import ops

    bucket = None if batch is None else ops.batch_bucket(batch)
    lead = () if bucket is None else (bucket,)
    (in_name,) = art.source.graph_inputs
    env = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
           for k, v in params.items()}
    env[in_name] = jax.ShapeDtypeStruct(lead + x.shape, x.dtype)
    texts = []
    for g in art.design.groups:
        fn = ops.cached_executable(g, batch=bucket)
        check(fn is not None,
              f"{g.name}: art.run cached no executable (batch {bucket})")
        texts.append(fn.lower(env).as_text())
        env.update(jax.eval_shape(fn, env))
    return texts


def int8_inputs(shape, n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(-128, 128, size=shape, dtype=np.int8)
            for _ in range(n)]


def serve_all(art, params, xs, bursts, config):
    """Submit ``xs`` to a ``ServeEngine`` in ``bursts`` (each burst
    waits for its answers) → (answers, batch sizes)."""
    from repro.serve import ServeEngine

    answers = []
    with ServeEngine(art, config, params=params) as eng:
        for size in bursts:
            futs = [eng.submit(x)
                    for x in xs[len(answers):len(answers) + size]]
            answers += [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]
        records = eng.flight_records()
        rejected = sum(
            row["value"] for row in
            eng.metrics()["counters"]["serve_rejected_total"]["values"])
    check(len(answers) == len(xs), f"{len(answers)}/{len(xs)} answered")
    check(rejected == 0, f"serve_rejected_total = {rejected}")
    bad = [r for r in records if r["outcome"] != "ok"]
    check(not bad, f"batches failed: {bad}")
    sizes = [r["n"] for r in records]
    check(sum(sizes) == len(xs), f"flight records cover {sum(sizes)} "
                                 f"of {len(xs)} requests")
    return answers, sizes


def check_exact(got, want, what):
    for i, (g, w) in enumerate(zip(got, want)):
        g = np.asarray(g)
        check(g.dtype == w.dtype and g.shape == w.shape,
              f"{what}[{i}]: {g.dtype}{g.shape} != {w.dtype}{w.shape}")
        check(np.array_equal(g, w),
              f"{what}[{i}]: {int(np.sum(g != w))} elements differ")


def phase_served_lenet5(model, art, xs, want):
    from repro.serve import ServeConfig

    config = ServeConfig(max_batch=16, latency_budget_ms=50.0)
    answers, sizes = serve_all(art, model.params, xs,
                               bursts=(1, 5, 16, 26), config=config)
    check(1 in sizes and config.max_batch in sizes
          and any(1 < n < config.max_batch for n in sizes),
          f"batch sizes {sizes} miss 1, a middle size or max_batch")
    check_exact(answers, want, "served_lenet5")
    return (f"{len(xs)} int8 requests in batches {sizes}, none rejected, "
            "all bit-exact with the CPU interpreter")


def phase_per_sample(name, model, art, x, want):
    check_exact([art.run(x, model.params)], [want], name)
    (text,) = executed_texts(art, model.params, x)
    check("tpu_custom_call" in text,
          f"{name}: no Pallas TPU kernel in the per-sample executable")
    return "unbatched art.run through the Pallas kernel, bit-exact"


def phase_resnet_tiny(model, art, xs, want):
    from repro.serve import ServeConfig

    answers, sizes = serve_all(art, model.params, xs, bursts=(len(xs),),
                               config=ServeConfig(max_batch=8,
                                                  latency_budget_ms=50.0))
    check_exact(answers, want, "served resnet_tiny")
    per_sample = phase_per_sample("resnet_tiny", model, art, xs[0], want[0])
    return (f"served {len(xs)} int8 requests in batches {sizes}, "
            f"bit-exact; {per_sample}")


def rel_errors(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    mx = float(np.abs(got - want).max() / np.abs(want).max())
    return l2, mx


def resnet18_strided(n_size=224):
    """ResNet-18's stride-2 convs (He et al. 2016, Table 1) at their
    published widths, chained: the 7×7/2 stem (+ReLU), a 2×2/2 max pool
    in place of the stem's padded 3×3/2 one, and the first 3×3/2 conv
    of conv3_x, conv4_x and conv5_x (+ReLU).  The stride-1 convs and
    the shortcuts are cut."""
    from repro.api import Conv2D, MaxPool, ReLU, Sequential

    return Sequential(
        [Conv2D(64, kernel=7, stride=2), ReLU(), MaxPool(2),
         Conv2D(128, stride=2), ReLU(), Conv2D(256, stride=2), ReLU(),
         Conv2D(512, stride=2), ReLU()],
        input_shape=(1, n_size, n_size, 3),
        name=f"resnet18_strided_{n_size}",
    ).build()


def float_setup(dfg, batch=4):
    """(artifact, float32 params, inputs of batch ``batch``, CPU
    reference per sample) for ``dfg`` on KV260."""
    from repro import CompileOptions, compile_graph

    art = compile_graph(dfg, CompileOptions(target="kv260"))
    src = art.source
    rng = np.random.default_rng(SEED)
    params = {}
    for k, v in sorted(src.values.items()):
        if v.is_constant:  # He-normal conv weights (KH, KW, Cin, Cout)
            fan_in = int(np.prod(v.shape[:-1]))
            params[k] = (rng.standard_normal(v.shape)
                         * np.sqrt(2.0 / fan_in)).astype(np.float32)
    (in_name,) = src.graph_inputs
    xb = rng.standard_normal((batch,) + tuple(src.values[in_name].shape)
                             ).astype(np.float32)
    return art, params, xb, reference(src, params, list(xb))


def phase_float(name, art, params, xb, want):
    got = [art.run(xb[0], params)] + list(art.run(xb, params))
    for b in (None, len(xb)):
        texts = executed_texts(art, params, xb[0], batch=b)
        missing = [g.name for g, t in zip(art.design.groups, texts)
                   if "tpu_custom_call" not in t]
        check(not missing,
              f"{name}: no Pallas TPU kernel in {missing} (batch {b})")
    want = [want[0]] + list(want)
    check(all(np.isfinite(g).all() for g in got), "non-finite output")
    errs = [rel_errors(g, w) for g, w in zip(got, want)]
    l2 = max(e[0] for e in errs)
    mx = max(e[1] for e in errs)
    check(l2 <= F32_REL_BOUND and mx <= F32_REL_BOUND,
          f"{name}: float32 error rel_l2={l2!r} max_rel={mx!r} > "
          f"{F32_REL_BOUND}")
    return (f"{len(art.design.groups)} groups, each with a Pallas kernel, "
            f"per sample + batch {len(xb)}; vs CPU reference "
            f"rel_l2={l2!r} max_rel={mx!r} (bound {F32_REL_BOUND})")


def main() -> int:
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX's default device is {dev.platform!r} "
              f"({dev.device_kind}); chip_smoke.py runs on a TPU only")
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: wall {time.perf_counter() - t0:.3f} s, compile 0.000 s: "
          f"platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}", flush=True)

    src_dir = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src_dir, "repro")):
        print(f"no repro package under {src_dir}: run chip_smoke.py from "
              "a checkout of the repository")
        return 1
    sys.path.insert(0, src_dir)
    from repro import CompileOptions, compile_cache, compile_graph, frontends

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    def phase(name, fn, *args):
        c0, t0 = clock.secs, time.perf_counter()
        what = fn(*args)
        print(f"{name}: wall {time.perf_counter() - t0:.3f} s, compile "
              f"{clock.secs - c0:.3f} s: {what}", flush=True)

    lenet = frontends.import_model(LENET5)
    lenet_art = compile_graph(lenet.dfg, CompileOptions(target="kv260"))
    (in_name,) = lenet.dfg.graph_inputs
    xs = int8_inputs(lenet.dfg.values[in_name].shape, 48, SEED)
    want = reference(lenet.dfg, lenet.params, xs)
    phase("served_lenet5", phase_served_lenet5, lenet, lenet_art, xs, want)
    phase("per_sample_lenet5", phase_per_sample, "per_sample_lenet5",
          lenet, lenet_art, xs[-1], want[-1])

    resnet = frontends.import_model(RESNET_TINY)
    resnet_art = compile_graph(resnet.dfg, CompileOptions(target="kv260"))
    (in_name,) = resnet.dfg.graph_inputs
    xs = int8_inputs(resnet.dfg.values[in_name].shape, 8, SEED + 1)
    want = reference(resnet.dfg, resnet.params, xs)
    phase("resnet_tiny", phase_resnet_tiny, resnet, resnet_art, xs, want)

    from repro.core import cnn_graphs

    for name, dfg in (("deep_cascade_224", cnn_graphs.deep_cascade(224)),
                      ("resnet18_strided", resnet18_strided())):
        setup = float_setup(dfg)
        check(len(setup[0].design.groups) == 4,
              f"{name}: {len(setup[0].design.groups)} groups, not 4")
        phase(name, phase_float, name, *setup)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
