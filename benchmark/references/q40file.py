"""Read tensors of a Q40 `.m` file as float32 `jax.numpy` arrays.

Shared by the reference families. The layout (header keys, tensor order) is
the file format's own, `dllama_tpu.formats`; the arithmetic is here: a Q40
block is an f16 scale and 16 bytes, element j in the low nibble of byte j and
element j + 16 in the high nibble, each minus 8, times the scale. The bytes
go to the device packed and are widened there, one tensor at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.formats.model_file import read_llm_header, tensor_plan
from dllama_tpu.formats.quants import FloatType


class Q40File:
    def __init__(self, path: str):
        self.header = read_llm_header(path)
        self.specs = {s.name: s for s in tensor_plan(self.header)}
        self._mm = np.memmap(path, dtype=np.uint8, mode="r")

    def _raw(self, name: str) -> np.ndarray:
        s = self.specs[name]
        return self._mm[s.offset : s.offset + s.nbytes]

    def rows_f32(self, name: str, rows) -> jnp.ndarray:
        """Chosen rows of an f32 matrix (the embedding), gathered on the host."""
        s = self.specs[name]
        table = self._raw(name).view(np.float32).reshape(s.shape)
        return jnp.asarray(table[np.asarray(rows)])

    def f32(self, name: str) -> jnp.ndarray:
        """A tensor in its file shape (out, in), float32 on the device."""
        s = self.specs[name]
        if s.float_type == FloatType.F32:
            return jnp.asarray(self._raw(name).view(np.float32).reshape(s.shape))
        if s.float_type != FloatType.Q40:
            raise ValueError(f"{name}: {s.float_type} is not read here")
        return self.f32_stack([name])[0]

    def f32_stack(self, names) -> jnp.ndarray:
        """Q40 tensors of one shape (an expert's matrix over all experts),
        stacked on a new first axis, in one transfer."""
        specs = [self.specs[n] for n in names]
        if {(s.float_type, s.shape) for s in specs} != {(FloatType.Q40, specs[0].shape)}:
            raise ValueError(f"{names[0]}...: not Q40 tensors of one shape")
        blocks = np.stack([self._raw(n).reshape(-1, 18) for n in names])
        blocks = blocks.reshape(-1, 18)
        scales = np.ascontiguousarray(blocks[:, :2]).view(np.float16)[:, 0]
        nibbles = np.ascontiguousarray(blocks[:, 2:])
        wide = _widen(jnp.asarray(scales), jnp.asarray(nibbles))
        return wide.reshape(len(names), *specs[0].shape)


@jax.jit
def _widen(scales, nibbles):
    lo = (nibbles & 0xF).astype(jnp.float32) - 8.0
    hi = (nibbles >> 4).astype(jnp.float32) - 8.0
    q = jnp.concatenate([lo, hi], axis=1)  # [n_blocks, 32]
    return q * scales.astype(jnp.float32)[:, None]
