"""The control: the reference put in the system's place, computed one
precision below the configuration's float32, in bfloat16 on the device.

``decode`` reconstructs samples from the reference's levels with bf16
reconstruction values and a bf16 inverse-DCT basis (float32 accumulation).
``in_place_of_decoder`` makes it the system's decoder for a whole run of
the harness, so that the run's own check has to come out not correct."""
from __future__ import annotations

import contextlib
from typing import Dict, List
from unittest import mock

import numpy as np

from . import reference as ref


def _bf16_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp

    out = jnp.matmul(jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return np.asarray(out)


def decode(blob: bytes, t: ref.RefTables) -> np.ndarray:
    p, levels = ref.decode_levels(blob, t)
    coeffs = t.grid()[np.arange(t.e)[None, :], levels.astype(np.int64)]
    return _bf16_matmul(coeffs, ref.idct_basis(t.n, t.e)).ravel()[: p.signal_length]


class _ControlBatch:
    """What ``BatchDecoder.decode`` returns while the control stands in."""

    def __init__(self, containers, tables: Dict[int, ref.RefTables]):
        self._blobs = [c.to_bytes() for c in containers]
        self._tables = tables

    def block_until_ready(self) -> "_ControlBatch":
        return self

    def to_host(self) -> List[np.ndarray]:
        return [decode(b, self._tables[ref.parse(b).domain_id])
                for b in self._blobs]


@contextlib.contextmanager
def in_place_of_decoder(tables: Dict[int, ref.RefTables]):
    """While open, every ``BatchDecoder.decode`` is the control's decode
    with the reference tables ``tables`` (by domain id)."""
    from repro.serving.batch_decode import BatchDecoder

    def stand_in(self, containers, _tables, **_):
        return _ControlBatch(containers, tables)

    with mock.patch.object(BatchDecoder, "decode", stand_in):
        yield
