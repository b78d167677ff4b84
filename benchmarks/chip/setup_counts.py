"""Operations and bytes of COPML's setup (Phases 1-2) at a configuration's
shapes, for the setup program's share of its roofline.

The count is the algorithm's work as `Copml.setup` specifies it: field
multiply-accumulates (MACs) of the Shamir sharing, the LCC encode, the
reconstruct and the secure X^T y, never the limb products, padding or
chunking of any implementation, so no rewrite of setup can move it.
m/K is rounded up, as the protocol's blocks are.
"""

from __future__ import annotations

from benchmarks.chip import counts


def setup_macs(n: int, k: int, t: int, m: int, d: int, c: int = 1) -> dict:
    """Field MACs of one setup, by phase.

    share:       Shamir sharing of X and of the targets y, N*T*m*(d + C);
    masks:       sharing of the T random blocks Z, N*T*T*(m/K)*d;
    encode:      each of N holders Lagrange-encodes its K data blocks and
                 T masks into N coded slices, N*N*(K+T)*(m/K)*d;
    reconstruct: each coded slice rebuilt from T+1 holders, N*(T+1)*(m/K)*d;
    xty:         each holder's local X^T y product, N*m*d*C;
    reduce:      BH08's degree reduction of X^T y: [rho]_T and [rho]_2T
                 dealt to N holders, the opening from 2T+1, (3NT+2T+1)*d*C;
    model:       sharing of the initial model, N*T*d*C.
    """
    mk = -(-m // k)
    dc = d * c
    out = {
        "share": n * t * m * (d + c),
        "masks": n * t * t * mk * d,
        "encode": n * n * (k + t) * mk * d,
        "reconstruct": n * (t + 1) * mk * d,
        "xty": n * m * dc,
        "reduce": (3 * n * t + 2 * t + 1) * dc,
        "model": n * t * dc,
    }
    out["total"] = sum(out.values())
    return out


def setup_bytes(n: int, k: int, m: int, d: int, c: int = 1) -> float:
    """Least bytes one setup must move: X and y read once, X~ written, and
    the X^T y and model shares written, each element at the field's bits."""
    mk = -(-m // k)
    elements = m * (d + c) + n * mk * d + 2 * n * d * c
    return elements * counts.BITS_PER_ELEMENT / 8


def least_setup_s(cfg: dict, device_kind: str) -> tuple:
    """(seconds, "compute" | "bytes"): the larger of the two bounds."""
    if cfg["mpc_mul"] != "bh08":
        raise ValueError(f"setup counts BH08's degree reduction, not "
                         f"{cfg['mpc_mul']!r}")
    shape = (cfg["n_clients"], cfg["k"], cfg["t"], cfg["m"], cfg["d"])
    macs = setup_macs(*shape)["total"]
    pk = counts.peaks(device_kind)
    compute = 2 * macs / pk["int8_ops_per_s"]
    memory = setup_bytes(cfg["n_clients"], cfg["k"], cfg["m"], cfg["d"]) \
        / pk["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "bytes")
