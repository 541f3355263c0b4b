"""Compile for a described TPU v5e, with no chip attached.

XLA's TPU compiler and Mosaic run here on shapes alone: the three Pallas
kernels forward and backward at published head and state widths, and the
depth-cut ``internvl2-2b`` train step that ``chip_smoke.py`` runs.  They
refuse what the chip would refuse (unaligned tiles, too much VMEM, a step
over HBM), which interpret mode on the CPU cannot show.  Nothing runs, so
nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.mamba_scan import mamba_scan_bsd
from repro.kernels.packed_flash_attention import packed_flash_attention_bkgsd
from repro.kernels.rwkv6_scan import rwkv6_scan_bhsm
from repro.models import mllm as mllm_lib
from repro.models.model import FwdCtx
from repro.train.optim import AdamWConfig, adamw_init
from repro.train.step import make_train_step

HBM_BYTES = 16 * 2**30                 # one v5e chip
SEQ = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for an absent chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(fn, arg shapes) at published widths, bf16 activations."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "packed_flash_attention":      # InternLM2-1.8B: H16/KH8/D128
        B, KH, G, D = 1, 8, 2, 128
        seg = _sds((B, SEQ), jnp.int32, sh)
        return (lambda q, k, v, s: packed_flash_attention_bkgsd(
                    q, k, v, s, s, causal=True),
                [_sds((B, KH, G, SEQ, D), bf16, sh),
                 _sds((B, KH, SEQ, D), bf16, sh),
                 _sds((B, KH, SEQ, D), bf16, sh), seg], 3)
    if name == "mamba_scan":                  # Jamba: d_inner 8192, N 16
        B, di, N = 1, 8192, 16
        return (mamba_scan_bsd,
                [_sds((B, SEQ, di), bf16, sh), _sds((B, SEQ, di), bf16, sh),
                 _sds((B, SEQ, N), bf16, sh), _sds((B, SEQ, N), bf16, sh),
                 _sds((di, N), f32, sh), _sds((di,), f32, sh)], 6)
    B, H, M = 1, 64, 64                       # RWKV6-7B: 64 heads x 64
    return (rwkv6_scan_bhsm,
            [_sds((B, H, SEQ, M), bf16, sh) for _ in range(4)]
            + [_sds((H, M), bf16, sh)], 5)


def _summed(fn):
    def go(*args):
        outs = fn(*args)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
    return go


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("kernel", ["packed_flash_attention", "mamba_scan",
                                    "rwkv6_scan"])
def test_kernel_compiles_for_v5e(one_chip, kernel, direction):
    fn, args, n_diff = _kernel_case(kernel, one_chip)
    if direction == "backward":
        fn = jax.grad(_summed(fn), argnums=tuple(range(n_diff)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


def test_internvl2_train_step_fits_one_v5e(one_chip):
    """The depth-cut train step of ``chip_smoke.py``: published widths, 4
    LLM + 4 ViT layers, 4 microbatches of one row (one 1024-patch image +
    1024 text tokens), f32 params and AdamW moments, donated."""
    full = get_config("internvl2-2b").desc
    desc = dataclasses.replace(
        full, encoder=dataclasses.replace(full.encoder, n_layers=4),
        llm=dataclasses.replace(full.llm, n_layers=4))

    def place(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = place(jax.eval_shape(
        lambda: mllm_lib.init(jax.random.PRNGKey(0), desc)))
    opt = place(jax.eval_shape(adamw_init, params))
    n_mb, T, E = 4, desc.stub.n_tokens, desc.stub.embed_dim
    batch = {"media_embeds": _sds((n_mb, 1, T, E), jnp.float32, one_chip),
             "media_mask": _sds((n_mb, 1, T), jnp.int32, one_chip),
             "text_tokens": _sds((n_mb, 1, 1024), jnp.int32, one_chip),
             "text_mask": _sds((n_mb, 1, 1024), jnp.int32, one_chip),
             "labels": _sds((n_mb, 1, 1024), jnp.int32, one_chip)}
    step = jax.jit(make_train_step(desc, AdamWConfig(),
                                   ctx=FwdCtx(mode="train")),
                   donate_argnums=(0, 1))
    compiled = step.lower(params, opt, batch,
                          _sds((), jnp.float32, one_chip)).compile()
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < live < HBM_BYTES, live
    # the donated params and moments are updated in place
    assert ma.alias_size_in_bytes > 0.9 * ma.output_size_in_bytes
