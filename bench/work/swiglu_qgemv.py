"""Work of one `swiglu_qgemv` call: the fused gate/up int4 GEMV.

The kernel streams both packed (d, f) weights (half a byte per weight)
and their f32 scales (one per 128 rows and column), reads the (m, d)
bfloat16 activations and writes the (m, f) bfloat16 product; it does
two (m, d) x (d, f) products.  m is the call's row count: the engine's
`max_batch` in decode.
"""
from __future__ import annotations

from typing import Dict

GROUP = 128


def per_call(dims: Dict, rows: int) -> Dict:
    d, f = dims["d"], dims["f"]
    weights = 2 * (d * f // 2 + 4 * (d // GROUP) * f)
    return {"flops": 4.0 * rows * d * f,
            "bytes": float(weights + 2 * rows * d + 2 * rows * f)}
