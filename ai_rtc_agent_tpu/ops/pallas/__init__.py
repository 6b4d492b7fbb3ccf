"""Pallas TPU kernels for the hot fused ops (see /opt/skills/guides/pallas_guide.md).

On a TPU the kernels compile through Mosaic; a kernel the compiler refuses
fails the build with the compiler's message.  Interpret mode exists for the
CPU test suite only and is chosen only where the CPU was asked for by name
(``JAX_PLATFORMS=cpu``): a process that wanted a chip and silently landed
on the CPU must not quietly interpret its kernels instead.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import logging
import math
import re

import jax

logger = logging.getLogger(__name__)


@functools.cache
def _log_interpret_mode() -> None:  # once per process
    logger.info("Pallas kernels run in interpret mode (JAX_PLATFORMS=cpu)")


def interpret_default() -> bool:
    """Interpret mode iff the backend is the CPU *and* the CPU was requested
    explicitly; a CPU nobody asked for is an error, any other backend
    compiles the kernel."""
    if jax.default_backend() != "cpu":
        return False
    if "cpu" not in (jax.config.jax_platforms or ""):
        raise RuntimeError(
            "a Pallas kernel was reached on a CPU backend nobody asked for "
            "(JAX_PLATFORMS is unset and no TPU was found); set "
            "JAX_PLATFORMS=cpu to run the kernels in interpret mode"
        )
    _log_interpret_mode()
    return True


# the ``name=`` each kernel in this package gives its pallas_call; the name
# rides the custom call's op_name through jit, vmap and the scalar-prefetch
# batching loop
KERNEL_NAMES = (
    "flash_attention", "fused_stream_epilogue", "joint_attention", "rope_attention",
)


def mosaic_kernel_counts(hlo_text: str) -> dict:
    """{kernel name -> number of Mosaic custom calls} in a compiled
    executable's HLO text (``compiled.as_text()``): the evidence that a
    step really contains the kernels its config names.  Interpret-mode and
    composed-XLA graphs have none."""
    counts: dict = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = line.partition('op_name="')[2].partition('"')[0]
        name = next((k for k in KERNEL_NAMES if k in op_name), "unnamed")
        counts[name] = counts.get(name, 0) + 1
    return counts


# an instruction of a compiled module: its name, its output's dtype and
# dimensions (a tuple-valued one does not match), its opcode
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
# what counts as the size of an activation: the served graphs' GroupNorms
# at 16x16 and up see 1.6e5 elements a row or more, the largest tensor of
# the tiny test models holds 3e4
_ACTIVATION_ELEMS = 100_000


def f32_relayout_copies(hlo_text: str) -> dict:
    """{"count", "bytes"} of the activation-sized float32 tensors a compiled
    executable (``compiled.as_text()``) writes only to hold the same numbers
    in another layout: outputs of ``copy`` instructions and of ``copy_*``
    fusions, outside fused computations.  What GroupNorm cost under XLA
    before PR 35: a float32 copy of the activation, transposed so that a
    group's channels lie together, and after a convolution over several
    rows two re-layouts of its float32 output.  A compile fact, beside
    ``mosaic_kernel_counts``: which of the two a step holds."""
    count = total = 0
    fused = False
    for line in hlo_text.splitlines():
        if line[:1].strip():  # a computation's header (or its closing brace)
            fused = "fused_computation" in line.partition("(")[0]
            continue
        m = None if fused else _INSTRUCTION.match(line)
        if m is None:
            continue
        name, dtype, dims, opcode = m.groups()
        if dtype != "f32" or not (
            opcode == "copy"
            or opcode == "fusion" and "copy" in name.partition(".")[0].split("_")
        ):
            continue
        elems = math.prod(int(d) for d in dims.split(",") if d)
        if elems > _ACTIVATION_ELEMS:
            count += 1
            total += 4 * elems
    return {"count": count, "bytes": total}


# an instruction with its output's layout: name, dtype, dimensions, the
# minor-to-major order of the dimensions, opcode
_LAID_OUT = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\{([\d,]*)[^}]*\} ([\w\-]+)\("
)
# a lane-sparse tensor worth counting, in elements a frame: TAESD's width-64
# activations hold 2.6e5 to 1.7e7 a frame at 512x512 (counted from 128x128
# up), a 512x512 RGB frame 7.9e5 (its ends, not counted)
_LANE_SPARSE_ELEMS = 1_000_000
LANES = 128  # the minor dimension of a TPU vector register's (8, 128) tile
# opcodes that write nothing of their own
_NO_WRITE = ("parameter", "constant", "bitcast", "get-tuple-element", "tuple")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}


def lane_sparse_outputs(hlo_text: str, frames: int = 1) -> dict:
    """{"count", "bytes"} of the large tensors (at least 1e6 elements for
    each of the ``frames`` a step carries) a compiled executable
    (``compiled.as_text()``) writes outside fused computations with a
    minor-most layout dimension under 128: the lane axis of each (8, 128)
    tile is then partly empty, so the tensor takes more HBM and VMEM than
    its bytes (counted here at its dtype's size, padding left out) and
    every pass over it uses part of each vector.  What TAESD's width-64
    chain held before it ran on a lane-folded grid (``models/taesd.py``); a
    compile fact beside ``f32_relayout_copies``."""
    count = total = 0
    fused = False
    for line in hlo_text.splitlines():
        if line[:1].strip():  # a computation's header (or its closing brace)
            fused = "fused_computation" in line.partition("(")[0]
            continue
        m = None if fused else _LAID_OUT.match(line)
        if m is None:
            continue
        _, dtype, dims, layout, opcode = m.groups()
        if opcode in _NO_WRITE or not dims or not layout:
            continue
        shape = [int(d) for d in dims.split(",")]
        elems = math.prod(shape)
        minor = shape[int(layout.split(",")[0])]
        if elems >= _LANE_SPARSE_ELEMS * frames and minor < LANES:
            count += 1
            total += _DTYPE_BYTES.get(dtype, 4) * elems
    return {"count": count, "bytes": total}


# who is counting the attention calls traced right now
_ATTENTION_PATHS: contextvars.ContextVar = contextvars.ContextVar(
    "attention_paths", default=None
)


@contextlib.contextmanager
def count_attention_paths():
    """-> a Counter of the ``flash_attention`` calls traced inside the
    block, by the layout their operands took: ``packed`` (``[B, L, H*D]``,
    as the projections leave them) or ``per_head`` (transposed to a head a
    program), of the ``joint_attention`` calls (the MMDiT's, 24 a pass at
    SD3's depth: ``joint_kloop`` where K is met in blocks, ``joint`` where
    it is one block) and of the ``rope_attention`` calls (FLUX's, 15 a
    pass at ``fluxschnell2x512``'s depth: ``rope_kloop`` where K is met in
    blocks, ``rope`` where it is one block).  A jitted function's Python body runs when
    it is traced, so wrap its ``lower()``: the evidence, beside
    ``mosaic_kernel_counts``, of which kernel variant a compiled step
    holds."""
    counts = collections.Counter()
    token = _ATTENTION_PATHS.set(counts)
    try:
        yield counts
    finally:
        _ATTENTION_PATHS.reset(token)


def note_attention_path(path: str) -> None:
    """One attention kernel call traced down ``path``, for whoever counts."""
    counts = _ATTENTION_PATHS.get()
    if counts is not None:
        counts[path] += 1
