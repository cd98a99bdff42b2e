"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The phase functions run end to end here with the CPU as both the device
and the reference (interpret-mode kernels, one virtual shard), which finds
wrong paths, arguments and control flow without the chip.  ``main()`` itself
must refuse the CPU backend: it exits non-zero and prints no result.
"""
import dataclasses
import importlib.util
import json
import os
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

TINY = dict(
    strips=10, median=1 << 14, lo=1 << 14, hi=1 << 15, min_total=1 << 17,
    serve_windows=8, kv_batch=1, kv_tokens=64,
)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ctx(smoke):
    return smoke.Context(
        devices=None, cpu=jax.devices("cpu")[0], seed=0, mosaic=False
    )


@pytest.fixture(scope="module")
def archive(smoke, ctx):
    sizes = smoke.Sizes(**TINY)
    arc = smoke.make_archive(sizes, ctx.seed)
    assert len(arc.signals) == sizes.strips
    assert sum(len(s) for s in arc.signals) >= sizes.min_total
    assert all(sizes.lo <= len(s) <= sizes.hi for s in arc.signals)
    return arc


def test_archive_phases_rehearsal(smoke, ctx, archive):
    clock = smoke.Clock()
    clock.phase("ingest", smoke.phase_ingest, ctx, archive)
    clock.phase("drain", smoke.phase_drain, ctx, archive, 1)
    clock.phase("migrate", smoke.phase_migrate, ctx, archive)
    clock.phase("kernels", smoke.phase_kernels, ctx, archive)
    clock.phase(
        "sharded", smoke.phase_sharded,
        dataclasses.replace(ctx, devices="auto"), archive, jax.devices()[:1],
    )


def test_serve_and_kv_phases_rehearsal(smoke, ctx):
    sizes = smoke.Sizes(**TINY)
    clock = smoke.Clock()
    clock.phase("serve", smoke.phase_serve, ctx, sizes)
    clock.phase("kv", smoke.phase_kv, ctx, sizes)


def test_strip_lengths_reach_the_archive_floor(smoke):
    lengths = smoke.strip_lengths(smoke.FULL, seed=0)
    assert len(lengths) == 64
    assert lengths.sum() >= 1 << 26
    assert lengths.min() >= 1 << 18 and lengths.max() <= 1 << 22


def test_main_refuses_a_cpu_backend(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "no CPU fallback" in out.err
    for line in out.out.splitlines():
        assert not line.startswith("{") or not json.loads(line).get("ok")
