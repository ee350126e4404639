"""The assigned input-shape set (LM-family: seq_len × global_batch).

``decode_*`` / ``long_*`` lower ``serve_step`` (one new token against a
seq_len KV cache); the others lower ``train_step`` / prefill.

long_500k requires sub-quadratic attention: run for SSM / hybrid /
windowed archs, skip for pure full-attention archs (list below, per the
brief; rationale in DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

#: Pure full-attention archs: every layer would need the full 500k KV and
#: the architecture defines no sub-quadratic mechanism -> skip long_500k.
LONG_CONTEXT_SKIP = frozenset(
    {
        "musicgen-large",
        "tinyllama-1.1b",
        "starcoder2-7b",
        "starcoder2-3b",
        "dbrx-132b",
        "qwen2-vl-2b",
        "sdar-30b-a3b",
    }
)


def applicable_shapes(arch: str) -> list[str]:
    out = []
    for name in SHAPES:
        if name == "long_500k" and arch in LONG_CONTEXT_SKIP:
            continue
        out.append(name)
    return out
