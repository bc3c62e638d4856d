"""Threshold ECDSA over a signer SUBSET of a Shamir universe, in Python
integers: the plain reference of ``tests/test_gg18_subset_quorum.py`` (and
of the degraded GG18 cell's tests). No JAX, no batching, no wire, no
Paillier: what GG18 (Gennaro and Goldfeder 2018, section 4.2) computes once
every MtA has done its job, written down for the signers S of a t-of-n
universe whose member of rank i holds the share f(i + 1). Curve points and
modular inverses are ``core.hostmath``'s (affine, Python ``int``); the
Lagrange weights are this file's own, not ``hostmath.lagrange_coeff``'s,
which the party under test uses.

Notation, GG18's: the signers' additive key shares w_i = λ_i · x_i with λ_i
the Lagrange weight of i over S at 0, so Σ_{i∈S} w_i = x; their public
forms W_j = λ_j · X_j with X_j = Σ_k (j's x)^k · C_k from the Feldman
commitments; k = Σ k_i, γ = Σ γ_i, δ = k·γ, σ = k·x; Γ = Σ γ_i·G;
R = δ⁻¹ · Γ (= k⁻¹ · G), r = R.x mod n; s_i = m·k_i + r·σ_i and
s = Σ s_i = m·k + r·σ.

Departures from GG18's text, each the program's too: (1) s is normalised to
the low half of the order (s → n − s where s > n/2; Bitcoin's and
Ethereum's rule, not GG18's), and the recovery id's parity bit flips with
it; (2) the recovery id (parity of R.y, bit 1 set where R.x ≥ n) is
returned beside (r, s). Nothing else: no share is left out of a sum, and
nothing is approximated.
"""
from typing import Dict, List, Sequence

from mpcium_tpu.core import hostmath as hm

N = hm.SECP_N


def lagrange_at_zero(xs: Sequence[int], x_i: int) -> int:
    """λ_i over the signers' ``xs`` at 0: Π_{j≠i} x_j / (x_j − x_i) mod n."""
    num = den = 1
    for x_j in xs:
        if x_j != x_i:
            num = num * x_j % N
            den = den * (x_j - x_i) % N
    return num * pow(den, -1, N) % N


def additive_shares(shares: Dict[str, int],
                    xs: Dict[str, int]) -> Dict[str, int]:
    """{signer: w_i = λ_i · x_i} over the signers ``shares`` names
    (``xs``: signer → its Shamir x, rank + 1 in the universe)."""
    at = [xs[p] for p in shares]
    return {p: lagrange_at_zero(at, xs[p]) * x % N for p, x in shares.items()}


def public_shares(commitments: List[hm.SecpPoint], signers: Sequence[str],
                  xs: Dict[str, int]) -> Dict[str, hm.SecpPoint]:
    """{signer: W_j = λ_j · Σ_k x_j^k · C_k} from the wallet's Feldman
    commitments C_0 .. C_t."""
    at = [xs[p] for p in signers]
    out = {}
    for p in signers:
        X = hm.SECP_INF
        for k, C in enumerate(commitments):
            X = hm.secp_add(X, hm.secp_mul(pow(xs[p], k, N), C))
        out[p] = hm.secp_mul(lagrange_at_zero(at, xs[p]), X)
    return out


def sign(m: int, k: Dict[str, int], gamma: Dict[str, int],
         w: Dict[str, int]) -> dict:
    """One signature by the signers ``k`` names, from every signer's
    nonces and additive key share. -> δ, σ, R, r, the unnormalised s, the
    low s, and the recovery id."""
    k_sum = sum(k.values()) % N
    gamma_sum = sum(gamma.values()) % N
    delta = k_sum * gamma_sum % N
    sigma = k_sum * (sum(w.values()) % N) % N
    Gamma = hm.SECP_INF
    for g in gamma.values():
        Gamma = hm.secp_add(Gamma, hm.secp_mul(g, hm.SECP_G))
    R = hm.secp_mul(pow(delta, -1, N), Gamma)
    r = R.x % N
    s = (m * k_sum + r * sigma) % N
    rec = (R.y & 1) | (2 if R.x >= N else 0)
    low = s
    if s > N // 2:
        low, rec = N - s, rec ^ 1
    return {"delta": delta, "sigma": sigma, "R": R, "r": r, "s": s,
            "s_low": low, "recovery": rec}


def partial(m: int, r: int, k_i: int, sigma_i: int) -> int:
    """s_i = m·k_i + r·σ_i: a signer's share of s from its share of σ."""
    return (m * k_i + r * sigma_i) % N
