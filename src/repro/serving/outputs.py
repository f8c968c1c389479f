"""One int32 slab a batch for a bucket executable's outputs.

The bucket executable packs its RankingOutput into a single (B, W)
int32 array on the device, so the completion worker copies a batch home
in one transfer instead of one per output. Columns, left to right:

  perm       int32 as is, m2 columns
  utility    float32 bit pattern, 1 column
  exposure   float32 bit patterns, K columns
  compliant  0 / 1, 1 column
  lam        float32 bit patterns, K columns

so W = m2 + 1 + K + 1 + K. The bit casts are exact both ways (NaN
payloads, -0.0 and inf survive), so the host's arrays are bitwise the
executable's own outputs. `OutputSlab` is the one place the layout is
written: `pack` runs inside the executable, `unpack` on the host.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.ranking import RankingOutput


@dataclass(frozen=True)
class OutputSlab:
    """Column layout of one bucket's output slab (bucket.m2 slots,
    bucket.K constraints)."""

    m2: int
    K: int

    def fields(self) -> tuple[tuple[str, type, int | slice], ...]:
        """(name, dtype, columns) of each RankingOutput field in slab
        order: an int column for a (B,) vector, a slice for a matrix."""
        spec = (("perm", np.int32, self.m2), ("utility", np.float32, None),
                ("exposure", np.float32, self.K),
                ("compliant", np.bool_, None), ("lam", np.float32, self.K))
        out, start = [], 0
        for name, dtype, width in spec:
            cols = start if width is None else slice(start, start + width)
            out.append((name, dtype, cols))
            start += 1 if width is None else width
        return tuple(out)

    @property
    def width(self) -> int:
        cols = self.fields()[-1][2]
        return cols.stop if isinstance(cols, slice) else cols + 1

    def pack(self, out: RankingOutput) -> jax.Array:
        """The (B, W) int32 slab of a batch's outputs; traced inside the
        bucket executable. An output of another dtype or shape than the
        layout's is refused rather than converted."""
        packed = []
        for name, dtype, cols in self.fields():
            x = getattr(out, name)
            want = x.shape[:1] + ((cols.stop - cols.start,)
                                  if isinstance(cols, slice) else ())
            if x.dtype != dtype or x.shape != want:
                raise ValueError(
                    f"output {name} is {x.dtype}{list(x.shape)}; the slab "
                    f"holds {np.dtype(dtype)}{list(want)}")
            if dtype is np.bool_:
                x = x.astype(jnp.int32)
            elif dtype is np.float32:
                x = lax.bitcast_convert_type(x, jnp.int32)
            packed.append(x.reshape(x.shape[0], -1))
        return jnp.concatenate(packed, axis=1)

    def unpack(self, slab: np.ndarray) -> RankingOutput:
        """The batch's RankingOutput from its slab on the host: views of
        the slab, but `compliant`, which is compared into a bool array."""
        return RankingOutput(**{
            name: slab[:, cols] != 0 if dtype is np.bool_
            else slab[:, cols].view(dtype)
            for name, dtype, cols in self.fields()})
