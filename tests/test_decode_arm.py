"""The decode engine's per-bucket arm choice (``use_kernels=None``).

On a CPU backend the automatic choice is always the XLA arm.  The tests
that stand in for a TPU patch the backend check of ``repro.kernels.ops``
and keep the kernel in interpret mode, so the kernel arm runs here with the
same bits it gives compiled.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import DOMAIN_DEFAULTS, calibrate, encode
from repro.data import make_signal
from repro.kernels import ops
from repro.serving import batch_decode
from repro.serving.batch_decode import BatchDecoder, default_decoder


@pytest.fixture(scope="module")
def power_tables():
    return calibrate(
        make_signal("load_power", 32768, seed=41), DOMAIN_DEFAULTS["power"],
        domain_id=0,
    )


@pytest.fixture(scope="module")
def meteo_tables():
    return calibrate(
        make_signal("temperature", 32768, seed=42),
        DOMAIN_DEFAULTS["meteorological"], domain_id=1,
    )


@pytest.fixture(scope="module")
def v3_tables(power_tables):
    cfg = power_tables.config.replace(
        predictor="delta", predict_bands=2, zero_planes=True
    )
    return dataclasses.replace(power_tables, config=cfg, domain_id=2)


@pytest.fixture
def tpu(monkeypatch):
    """A TPU backend as the arm choice sees it; the kernel itself still
    runs in interpret mode."""
    monkeypatch.delenv("FPTC_USE_KERNELS", raising=False)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interp", lambda: True)


def _containers(tables, name, lengths, seed):
    return [
        encode(make_signal(name, n, seed=seed + i), tables)
        for i, n in enumerate(lengths)
    ]


def _arms(dec):
    return {r["plan_key"][0]: r["arm"] for r in dec.stats.bucket_pad}


def _decode(containers, tables, use_kernels=None):
    dec = BatchDecoder(use_kernels=use_kernels, devices=None)
    return dec, dec.decode(containers, tables).to_host()


def _assert_bits_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_trivial_bucket_within_budget_takes_the_kernel(tpu, power_tables):
    cs = _containers(power_tables, "load_power", [4096, 3001], 50)
    dec, got = _decode(cs, power_tables)
    assert _arms(dec) == {0: "pallas"}
    assert dec.stats.kernel_dispatches == dec.stats.dispatches == 1
    _, want = _decode(cs, power_tables, use_kernels=False)
    _assert_bits_equal(got, want)


def test_v3_bucket_stays_on_xla(tpu, v3_tables):
    cs = _containers(v3_tables, "load_power", [5000, 777], 60)
    dec, _ = _decode(cs, v3_tables)
    assert _arms(dec) == {2: "xla"}
    assert dec.stats.kernel_dispatches == 0


def test_bucket_over_the_vmem_budget_stays_on_xla(tpu, monkeypatch,
                                                  power_tables):
    cs = _containers(power_tables, "load_power", [4096], 70)
    need = ops.decode_vmem_bytes(
        cs[0].num_windows, n=cs[0].n, e=cs[0].e, max_symlen=64
    )
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", need // 4)
    dec, out = _decode(cs, power_tables)  # raises nothing
    assert _arms(dec) == {0: "xla"}
    assert out[0].shape == (4096,)


@pytest.mark.parametrize("use_kernels,arm", [(False, "xla"), (True, "pallas")])
def test_explicit_use_kernels_forces_its_arm(tpu, monkeypatch, power_tables,
                                             v3_tables, use_kernels, arm):
    # forced even where the automatic choice would pick the other arm
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", 0)
    cs = _containers(power_tables, "load_power", [4096], 90)
    cs += _containers(v3_tables, "load_power", [3000], 91)
    dec, _ = _decode(cs, {0: power_tables, 2: v3_tables}, use_kernels)
    assert _arms(dec) == {0: arm, 2: arm}
    assert dec.stats.kernel_dispatches == (2 if use_kernels else 0)


def test_cpu_backend_always_takes_xla(monkeypatch, power_tables):
    monkeypatch.delenv("FPTC_USE_KERNELS", raising=False)
    assert not ops.on_tpu()
    cs = _containers(power_tables, "load_power", [4096], 100)
    dec, _ = _decode(cs, power_tables)
    assert dec.use_kernels is None
    assert _arms(dec) == {0: "xla"}
    assert dec.stats.kernel_dispatches == 0


def test_env_default_forces_the_kernel(monkeypatch):
    monkeypatch.setenv("FPTC_USE_KERNELS", "1")
    assert BatchDecoder(devices=None).use_kernels is True
    assert BatchDecoder(devices=None, use_kernels=False).use_kernels is False
    assert default_decoder().use_kernels is True
    monkeypatch.delenv("FPTC_USE_KERNELS")
    assert BatchDecoder(devices=None).use_kernels is None
    assert default_decoder().use_kernels is None


def _need(containers, max_symlen):
    c = containers[0]
    return ops.decode_vmem_bytes(
        batch_decode.p2(sum(x.num_windows for x in containers)),
        n=c.n, e=c.e, max_symlen=max_symlen,
    )


def test_mixed_batch_matches_the_xla_arm_bit_for_bit(tpu, monkeypatch,
                                                     power_tables,
                                                     meteo_tables, v3_tables):
    """A meteorological bucket on the kernel, a power bucket past the
    (lowered) VMEM budget and a v3 bucket on XLA: the same bits as an
    all-XLA decode, in the caller's order."""
    meteo = _containers(meteo_tables, "temperature", [20000, 6000], 110)
    power = _containers(power_tables, "load_power", [4096, 333, 9000], 120)
    # E=6's block-diagonal basis outweighs these small dense streams
    budget = _need(power, 1) - 1
    assert _need(meteo, 64) <= budget
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", budget)
    v3 = _containers(v3_tables, "load_power", [5000, 64], 130)
    cs = [power[0], meteo[0], v3[0], power[1], meteo[1], v3[1], power[2]]
    tables = {0: power_tables, 1: meteo_tables, 2: v3_tables}
    dec, got = _decode(cs, tables)
    assert _arms(dec) == {0: "xla", 1: "pallas", 2: "xla"}
    assert dec.stats.kernel_dispatches == 1 and dec.stats.dispatches == 3
    _, want = _decode(cs, tables, use_kernels=False)
    _assert_bits_equal(got, want)


def test_kernel_fits_answers_what_the_wrapper_refuses():
    kw = dict(n=32, e=16, l_max=16, max_symlen=32)
    assert ops.decode_kernel_fits(1 << 21, 1 << 20, **kw)
    assert not ops.decode_kernel_fits(
        1 << 21, 1 << 20, coding=(1, 2, True), **kw
    )
    # past the budget: the wrapper's own check raises for the same bucket
    assert not ops.decode_kernel_fits(1 << 22, 1 << 22, **kw)
    with pytest.raises(ops.KernelLimitError):
        ops.check_decode_vmem(1 << 22, n=32, e=16, max_symlen=32)
    # past the int32 symbol offsets
    assert not ops.decode_kernel_fits(1 << 22, 1 << 28, **kw)
