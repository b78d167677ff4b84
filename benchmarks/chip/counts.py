"""Operations and bytes of one COPML training step, and the chip's peaks.

The count is the algorithm's work at the configuration's shapes: field
multiply-accumulates (MACs) of Phases 2-4 as `Copml.iteration` specifies
them, never the limb products, padding or reductions of any
implementation, so no rewrite of the step can move it.
"""

from __future__ import annotations

BITS_PER_ELEMENT = 26          # a residue of p = 2^26 - 5

# Published per-chip peaks, keyed by jax's device_kind.  Source: Google
# Cloud documentation, "TPU v5e" (393 TOP/s int8, 819 GB/s HBM).
PEAKS = {
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def step_macs(n: int, k: int, t: int, mk: int, d: int, c: int = 1) -> dict:
    """Field MACs of one iteration, by phase.

    encode:      each of N holders Lagrange-encodes its share of w (in K
                 slots) and T masks into N coded models, N*N*(K+T)*dw;
    reconstruct: the coded models rebuilt from the N holders, N*N*dw;
    gradient:    each client's X~_i w~_i and X~_i^T ghat(.), 2*N*mk*d*C;
    mix:         the zero-sharing of the N coded gradients, N*N*T*dw;
    decode:      each holder's decode of the N shared gradients, N*N*dw;
    truncate:    TruncPr's shares of r and r0, 2*N*T*dw.
    """
    dw = d * c
    out = {
        "encode": n * n * (k + t) * dw,
        "reconstruct": n * n * dw,
        "gradient": 2 * n * mk * d * c,
        "mix": n * n * t * dw,
        "decode": n * n * dw,
        "truncate": 2 * n * t * dw,
    }
    out["total"] = sum(out.values())
    return out


def step_bytes(n: int, mk: int, d: int, c: int = 1) -> float:
    """Least bytes one iteration must move: X~ read once, and the model
    shares and X^T y shares read and the new model shares written, each
    element at the field's 26 bits."""
    elements = n * mk * d + 3 * n * d * c
    return elements * BITS_PER_ELEMENT / 8


def least_step_s(cfg: dict, device_kind: str) -> tuple:
    """(seconds, "compute" | "bytes"): the larger of the two bounds."""
    mk = -(-cfg["m"] // cfg["k"])
    macs = step_macs(cfg["n_clients"], cfg["k"], cfg["t"], mk, cfg["d"])
    pk = peaks(device_kind)
    compute = 2 * macs["total"] / pk["int8_ops_per_s"]
    memory = step_bytes(cfg["n_clients"], mk, cfg["d"]) / pk["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "bytes")
