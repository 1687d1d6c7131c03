"""Compiles of the main path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and raises what the chip's compiler
would raise (Mosaic refusals, VMEM overflows).  Each case is one kernel
or one group executable at its real width, about a second; LeNet-5
conv 1 in its one 32-row block takes about 13 s.

The topology is described inside module-scoped fixtures, never at
import: only one process at a time may load the TPU library, so with
several pytest workers the worker that runs this file loads it and
keeps it.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api, frontends
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENET5 = os.path.join(REPO, "tests", "golden", "lenet5.onnx")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the TPU library otherwise writes its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    """``compile_tpu(fn, *shapes)`` → HLO text of ``jit(fn)`` compiled
    for one v5e chip; ``shapes`` are ``(shape, dtype)`` pairs or dicts
    of them."""
    def struct(spec):
        if isinstance(spec, dict):
            return {k: struct(v) for k, v in spec.items()}
        shape, dtype = spec
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_(fn, *specs):
        args = [struct(s) for s in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


def _conv(stride=1, padding="SAME", epilogue=None):
    def fn(x, w):
        return ops.conv2d_stream(x, w, stride=stride, padding=padding,
                                 epilogue=epilogue, interpret=False)
    return fn


def _lenet5_group_env(dtype, batch=None):
    """(group, env specs) of the LeNet-5 golden's one group with every
    value in ``dtype`` (inputs batched at ``batch``)."""
    art = api.compile_graph(frontends.import_model(LENET5).dfg,
                            api.CompileOptions(target="kv260"))
    (group,) = art.design.groups
    src = group.dfg
    lead = () if batch is None else (batch,)
    env = {
        k: ((() if v.is_constant else lead) + tuple(v.shape), dtype)
        for k, v in src.values.items()
        if v.is_constant or k in src.graph_inputs
    }
    return group, env


def test_lenet5_conv1_int8_streams_through_pallas(compile_tpu):
    hlo = compile_tpu(_conv(padding="VALID"),
                      ((1, 32, 32, 1), jnp.int8), ((5, 5, 1, 6), jnp.int8))
    assert "tpu_custom_call" in hlo


def test_lenet5_int32_per_sample_group_takes_xla(compile_tpu):
    """int32 operands have no Mosaic matmul: the per-sample group
    lowers its convs with ``conv2d_same_mm`` and compiles."""
    group, env = _lenet5_group_env(jnp.int32)
    hlo = compile_tpu(ops.lower_group(group, interpret=False, jit=False),
                      env)
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_deep_cascade_224_conv(compile_tpu, dtype):
    hlo = compile_tpu(_conv(epilogue="relu"),
                      ((1, 224, 224, 136), dtype),
                      ((3, 3, 136, 136), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_stride2_conv(compile_tpu, dtype):
    hlo = compile_tpu(_conv(stride=2),
                      ((1, 32, 32, 16), dtype), ((3, 3, 16, 32), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("x_shape, w_shape", [
    ((1, 224, 224, 3), (7, 7, 3, 64)),
    ((1, 56, 56, 64), (3, 3, 64, 128)),
], ids=["stem_224x3", "conv3_56x64"])
def test_resnet18_stride2_conv_f32(compile_tpu, x_shape, w_shape):
    """ResNet-18's stride-2 convs at their published widths: the 7×7
    stem and the first conv of conv3_x."""
    hlo = compile_tpu(_conv(stride=2, epilogue="relu"),
                      (x_shape, jnp.float32), (w_shape, jnp.float32))
    assert "tpu_custom_call" in hlo


def test_vmapped_bucket8_float_group(compile_tpu):
    """The served float path: LeNet-5's group vmapped at bucket 8."""
    group, env = _lenet5_group_env(jnp.float32, batch=8)
    hlo = compile_tpu(
        ops.lower_group(group, interpret=False, jit=False, batch=8), env)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("h,c,stride", [
    (112, 32, 1), (112, 96, 2), (28, 192, 2), (14, 576, 1), (7, 960, 1),
], ids=["112x32", "112x96_s2", "28x192_s2", "14x576", "7x960"])
def test_mobilenet_v2_depthwise_bucket64(compile_tpu, h, c, stride):
    """MobileNetV2's depthwise convs at their published widths, with
    the folded bias and ReLU6, vmapped at the top bucket: the VPU
    kernel, under its own name."""
    def fn(x, w, b):
        return ops.dwconv2d_stream(x, w, b, stride=stride, epilogue="relu6",
                                   interpret=False)

    hlo = compile_tpu(jax.vmap(fn, in_axes=(0, None, None)),
                      ((64, 1, h, h, c), jnp.float32),
                      ((3, 3, c), jnp.float32), ((c,), jnp.float32))
    assert "tpu_custom_call" in hlo and "ming_dwconv2d_stream" in hlo


def _kernel_operands(hlo: str, name: str) -> int:
    (line,) = [ln for ln in hlo.splitlines()
               if name in ln.split(" = ")[0] and "tpu_custom_call" in ln]
    return line.split("custom-call(", 1)[1].split(")", 1)[0].count("%")


def test_stream_conv_takes_a_bias_only_where_one_is_fused(compile_tpu):
    """Without a bias the stream kernel keeps its two operands (the
    executables of a bias-free model, VGG-16's, are what they were);
    a fused bias is a third, added in the kernel."""
    x, w = ((1, 56, 56, 24), jnp.float32), ((1, 1, 24, 144), jnp.float32)
    plain = compile_tpu(_conv(epilogue="relu"), x, w)
    assert _kernel_operands(plain, "ming_conv2d_stream") == 2

    def biased(x, w, b):
        return ops.conv2d_stream(x, w, b, epilogue="relu6", interpret=False)

    hlo = compile_tpu(biased, x, w, ((144,), jnp.float32))
    assert _kernel_operands(hlo, "ming_conv2d_stream") == 3
