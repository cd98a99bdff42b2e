"""The limits of ``correct`` separate the system from the control on the
CPU at a small size: a sound run reads under every limit, and a run of the
harness with the control (the reference in bfloat16) standing in for the
system's decoder comes out not correct, over one of them."""
import pytest

from _cells import CELLS, run, tiny


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run(cell, 4_000_000_123)
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "setup_s" in r["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    import control
    from fptcbench import spec

    limits = spec.load_cell(cell).traffic["limits"]
    program, ctl = control.readings(cell, 2_222_222_222, 1.0,
                                    require_chip=False, overrides=tiny(cell))
    assert program["correct"] is True, program
    assert ctl["correct"] is False, ctl
    assert any(ctl[n] > limits[n] for n in limits), (ctl, limits)
