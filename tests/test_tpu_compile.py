"""The megakernels compiled by Mosaic for a described TPU v5e, with no chip.

These are the only compiles for a TPU in the suite: the decode megakernel at
the four paper-domain widths, the encode pack kernel, and the VMEM limit the
kernel wrappers enforce.  They catch what interpret mode cannot (tiling,
lowering and VMEM refusals) at no chip time.  The topology is described in
a fixture, never at import, so every test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import DOMAIN_DEFAULTS
from repro.core.symlen import chunk_words_bound
from repro.kernels import decode_fused as df
from repro.kernels import encode_fused as ef
from repro.kernels import ops

DOMAINS = ["biomedical", "seismic", "power", "meteorological"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one, so keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_decode(sharding, cfg, num_windows, words, max_symlen=64):
    def run(hi, lo, sl, limit, first, rank, syms, lut, rscale):
        return df.decode_fused(
            hi, lo, sl, limit, first, rank, syms, lut, rscale,
            l_max=cfg.l_max, max_symlen=max_symlen,
            num_windows=num_windows, n=cfg.n, e=cfg.e,
            vmem_limit_bytes=ops.VMEM_LIMIT_BYTES, interpret=False,
        )

    s = lambda shape, dt: _spec(sharding, shape, dt)  # noqa: E731
    args = [
        s((words,), jnp.uint32), s((words,), jnp.uint32),
        s((words,), jnp.int32),
        s((cfg.l_max,), jnp.uint32), s((cfg.l_max + 1,), jnp.uint32),
        s((cfg.l_max + 1,), jnp.int32), s((256,), jnp.int32),
        s((cfg.e, 256), jnp.float32), s((), jnp.float32),
    ]
    return jax.jit(run).lower(*args).compile()


@pytest.mark.parametrize("domain", DOMAINS)
def test_decode_megakernel_compiles_for_v5e(one_chip, domain):
    cfg = DOMAIN_DEFAULTS[domain]
    compiled = _compile_decode(one_chip, cfg, num_windows=1024, words=4096)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("domain", ["biomedical", "seismic"])
def test_encode_pack_kernel_compiles_for_v5e(one_chip, domain):
    cfg = DOMAIN_DEFAULTS[domain]
    chunk = 1024

    def run(syms, codes, lengths):
        return ef.encode_pack(
            syms, codes, lengths,
            word_slots=chunk_words_bound(chunk, cfg.l_max),
            block_lanes=ef.BLOCK_LANES,
            vmem_limit_bytes=ops.VMEM_LIMIT_BYTES, interpret=False,
        )

    compiled = jax.jit(run).lower(
        _spec(one_chip, (chunk, 4 * ef.BLOCK_LANES), jnp.int32),
        _spec(one_chip, (256,), jnp.uint32),
        _spec(one_chip, (256,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_vmem_refusal_just_past_the_limit(one_chip):
    """The wrapper admits a bucket filling its VMEM budget, which the
    compiler accepts, and refuses the next window block with the typed
    error naming the limit."""
    cfg = DOMAIN_DEFAULTS["power"]
    kw = dict(n=cfg.n, e=cfg.e, max_symlen=64)
    step = df.window_align(cfg.e)
    lo, hi = step, 1 << 24
    while hi - lo > step:  # the last window count check_decode_vmem admits
        mid = (lo + hi) // 2 // step * step
        if ops.decode_vmem_bytes(mid, **kw) <= ops.VMEM_BUDGET_BYTES:
            lo = mid
        else:
            hi = mid
    ops.check_decode_vmem(lo, **kw)
    with pytest.raises(ops.KernelLimitError, match="VMEM_BUDGET_BYTES"):
        ops.check_decode_vmem(lo + step, **kw)
    compiled = _compile_decode(one_chip, cfg, num_windows=lo, words=4096)
    assert "tpu_custom_call" in compiled.as_text()
