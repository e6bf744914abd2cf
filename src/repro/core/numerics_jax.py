"""Device-side compression math: batched whitening + whitened SVD + refine.

This is the jit-compiled counterpart of ``core.numerics`` (which stays the
host fp64 precision oracle; see tests/test_compress_device.py). Everything
here runs in fp32 — no fp64 anywhere, so the same code path compiles for
TPU — and is batched over a leading group axis so a whole bucket of
same-shaped matrices decomposes in ONE call instead of a host loop.

The decomposition avoids rectangular SVD entirely: with ``M = S·W_cat`` the
whitened factorization is recovered from the eigendecomposition of the
SMALL-side Gram,

    d1 <= n·d2 :  K = S (W Wᵀ) Sᵀ = M Mᵀ   (d1, d1)
                  B = S⁻¹ U_k Σ_k,   C = Σ_k⁻¹ U_kᵀ M = (S U_k)ᵀ W / σ
    d1 >  n·d2 :  K = Mᵀ M                  (n·d2, n·d2)
                  B = S⁻¹ M V_k = W V_k,    C = V_kᵀ

so the only cubic-cost op is a (min-side)² eigh while every large-dimension
contraction is a plain GEMM — the shape regime where the host fp64
rectangular SVD is slowest (wide shared-basis groups, fused MoE experts) is
exactly where this wins the most. The full singular spectrum (every nonzero
σ, identical in count to ``numpy.linalg.svd``) comes out of the same eigh,
so effective-rank allocation sees the same input as the oracle.

For very large min-sides the exact eigh itself dominates; ``rsvd > 0``
switches to a randomized range-finder (Halko et al.: Gaussian sketch +
subspace iterations + small eigh) that only pays GEMMs in the large
dimensions. Its top-(k+oversample) estimates are approximate, but the
truncated tail energy is restored exactly via the trace identity
(``_dec_rsvd``), so rank allocation sees a full-length spectrum with the
right total energy (DESIGN.md §1.5).

Precision: every public entry point runs under
``jax.default_matmul_precision("highest")``. The operands are fp32 Grams
and weights, not bf16 activations; at the TPU's default matmul precision
they would be rounded to bf16 inside every dot, far below the fp64
oracle's parity bar. On the CPU fp32 dots are exact fp32 either way.

Structure note: the pipeline is deliberately split into SEVERAL small
jitted stages instead of one fused jit. XLA:CPU runs the dense dots in a
computation noticeably slower when the same executable also contains
LAPACK custom calls (cholesky/eigh/qr/trsm), so factorizations and GEMMs
live in separate executables; intermediates are jax arrays and never leave
the device, and each stage still batches the whole bucket.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

MAX_DAMP_TRIES = 12          # matches numerics.cholesky_whitener


def _highest(fn):
    """Run ``fn`` (and trace every jitted stage it calls) at full fp32
    matmul precision."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return run


# ---------------------------------------------------------------------------
# Whitening: batched Cholesky with per-matrix damping escalation
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("max_tries",))
def cholesky_escalate(G: jax.Array, damp: float = 1e-6,
                      max_tries: int = MAX_DAMP_TRIES
                      ) -> Tuple[jax.Array, jax.Array]:
    """Batched damped Cholesky ``L Lᵀ = G + τI`` with the same ×10
    escalation policy as the host oracle, vectorized per batch member:
    XLA's cholesky reports failure as NaNs, so members whose factorization
    failed get their τ bumped and re-factored while already-converged
    members keep their τ. Returns ``(L, tau)`` with L lower-triangular;
    a still-NaN L after ``max_tries`` means the Gram itself was non-finite
    (the caller's factors will surface it).
    """
    G = G.astype(jnp.float32)
    d = G.shape[-1]
    G = 0.5 * (G + jnp.swapaxes(G, -1, -2))
    eye = jnp.eye(d, dtype=G.dtype)
    tr = jnp.trace(G, axis1=-2, axis2=-1) / d
    tau0 = damp * jnp.maximum(tr, 1e-12)

    def factor(tau):
        return jnp.linalg.cholesky(G + tau[..., None, None] * eye)

    def ok(L):
        return jnp.isfinite(L).all(axis=(-2, -1))

    def cond(state):
        i, _tau, L = state
        return jnp.logical_and(i < max_tries, jnp.logical_not(ok(L).all()))

    def body(state):
        i, tau, L = state
        tau = jnp.where(ok(L), tau, tau * 10.0)
        return i + 1, tau, factor(tau)

    _, tau, L = jax.lax.while_loop(cond, body, (0, tau0, factor(tau0)))
    return L, tau


@jax.jit
def _fix_factor(R: jax.Array) -> jax.Array:
    """Normalize a streamed upper-triangular factor the way the host's
    ``numerics.whitener_from_factor`` does: fix the QR sign ambiguity by
    making the diagonal positive, and floor the diagonal so rank-deficient
    calibration streams (fewer rows than d) don't make the triangular
    solves blow up."""
    R = R.astype(jnp.float32)
    d = R.shape[-1]
    dia = jnp.diagonal(R, axis1=-2, axis2=-1)
    s = jnp.sign(dia)
    s = jnp.where(s == 0, 1.0, s)
    R = R * s[..., :, None]
    dia = jnp.abs(dia)
    floor = 1e-7 * jnp.maximum(dia.max(axis=-1, keepdims=True), 1e-30)
    return R + (jnp.maximum(dia, floor) - dia)[..., :, None] \
        * jnp.eye(d, dtype=jnp.float32)


@_highest
@jax.jit
def combine_factors(Rs: jax.Array) -> jax.Array:
    """Merge per-member streaming-whitening factors into one group factor:
    ``Rs (b, n, d, d)`` with ``R_iᵀR_i = G_i`` → R with ``RᵀR = Σ_i G_i``,
    via the R of a QR over the stacked factors (no Gram is ever formed)."""
    b, n, d, _ = Rs.shape
    stacked = Rs.astype(jnp.float32).reshape(b, n * d, d)
    return jnp.linalg.qr(stacked, mode="r")


@_highest
@jax.jit
def tree_reduce_factors(Rs: jax.Array) -> jax.Array:
    """Exact distributed-whitening reduction (DESIGN.md §1.6): merge
    per-shard streaming factors ``Rs (m, d, d)`` (R_iᵀR_i = G_i, one per
    data-parallel shard) into a single R with ``RᵀR = Σ_i G_i`` by
    PAIRWISE rounds ``R' = qr_r([R_a; R_b])``. Each round is an orthogonal
    transform of the stacked rows, so any reduction order yields the same
    RᵀR — the tree order keeps every QR at (2d, d), the per-hop shape a
    ring/tree reduction would run on a real mesh, and the result matches
    the single-shard QR chain up to fp rounding and row signs."""
    Rs = Rs.astype(jnp.float32)
    m = Rs.shape[0]
    while m > 1:
        half = m // 2
        pairs = jnp.concatenate([Rs[:half], Rs[half:2 * half]], axis=1)
        reduced = jnp.linalg.qr(pairs, mode="r")      # (half, d, d)
        if m % 2:
            reduced = jnp.concatenate([reduced, Rs[2 * half:]], axis=0)
        Rs = reduced
        m = Rs.shape[0]
    return Rs[0]


# ---------------------------------------------------------------------------
# Jitted stages (LAPACK ops and GEMMs deliberately in separate executables)
# ---------------------------------------------------------------------------
@jax.jit
def _eigh_desc(K: jax.Array) -> Tuple[jax.Array, jax.Array]:
    lam, V = jnp.linalg.eigh(K)
    return lam[..., ::-1], V[..., ::-1]


@jax.jit
def _nt_gram(X: jax.Array) -> jax.Array:
    """X Xᵀ batched, contraction over the (contiguous) last axis."""
    return jnp.einsum("bim,bjm->bij", X, X)


@jax.jit
def _sandwich(L: jax.Array, S: jax.Array) -> jax.Array:
    """Lᵀ S L (small square bmms)."""
    return jnp.einsum("bji,bjk->bik", L, jnp.einsum("bij,bjk->bik", S, L))


@jax.jit
def _tn_project(A: jax.Array, W: jax.Array) -> jax.Array:
    """Aᵀ W with A (b, d, k), W (b, d, m) → (b, k, m). The d-major layout
    of both operands is the fastest big-GEMM form XLA:CPU offers short of
    transposing W itself."""
    return jnp.einsum("bdk,bdm->bkm", A, W)


@jax.jit
def _solve_lower_t(L: jax.Array, Y: jax.Array) -> jax.Array:
    """L⁻ᵀ Y batched (L lower-triangular)."""
    return jax.vmap(lambda lo, y: jax.scipy.linalg.solve_triangular(
        lo, y, lower=True, trans=1))(L, Y)


@jax.jit
def _cho_solve(Lk: jax.Array, Y: jax.Array) -> jax.Array:
    """(Lk Lkᵀ)⁻¹ Y batched."""
    def one(lo, y):
        return jax.scipy.linalg.solve_triangular(
            lo, jax.scipy.linalg.solve_triangular(lo, y, lower=True),
            lower=True, trans=1)
    return jax.vmap(one)(Lk, Y)


@jax.jit
def _bmm(A: jax.Array, B: jax.Array) -> jax.Array:
    return jnp.einsum("bij,bjk->bik", A, B)


@jax.jit
def _qr_q(Y: jax.Array) -> jax.Array:
    return jnp.linalg.qr(Y)[0]


def _whiten_big(W, L, sL):
    """M = S W for the given whitener (None/None = identity)."""
    if L is not None:
        return _tn_project(L, W)             # Lᵀ W
    if sL is not None:
        return sL[:, :, None] * W
    return W


# ---------------------------------------------------------------------------
# Batched whitened decomposition
# ---------------------------------------------------------------------------
def _dec_left(W, L, sL, k):
    """d1 <= n·d2 case. Exactly one of L (cholesky lower factor) / sL
    (diag scale, (b, d1)) is given; both None means identity whitener."""
    if L is not None:
        K = _sandwich(L, _nt_gram(W))
    elif sL is not None:
        K = sL[:, :, None] * _nt_gram(W) * sL[:, None, :]
    else:
        K = _nt_gram(W)
    lam, U = _eigh_desc(K)
    sig = jnp.sqrt(jnp.clip(lam, 0.0))
    Uk = U[:, :, :k]
    sigk = sig[:, :k]
    inv_sig = (1.0 / jnp.maximum(sigk, 1e-20))[:, :, None]
    if L is not None:
        # C = (L Uk)ᵀ W / σ ; B = L⁻ᵀ (Uk Σ)  (S = Lᵀ ⇒ S⁻¹ = L⁻ᵀ)
        C = _tn_project(_bmm(L, Uk), W) * inv_sig
        B = _solve_lower_t(L, Uk * sigk[:, None, :])
    elif sL is not None:
        C = _tn_project(Uk * sL[:, :, None], W) * inv_sig
        B = (Uk * sigk[:, None, :]) / sL[:, :, None]
    else:
        C = _tn_project(Uk, W) * inv_sig
        B = Uk * sigk[:, None, :]
    return sig, B, C


def _dec_right(W, L, sL, k):
    """d1 > n·d2 case: eigh on the (n·d2)-side Gram. B = S⁻¹ M V_k = W V_k
    for ANY whitener, so no solve is needed."""
    M = _whiten_big(W, L, sL)
    K = _tn_project(M, M)
    lam, V = _eigh_desc(K)
    sig = jnp.sqrt(jnp.clip(lam, 0.0))
    Vk = V[:, :, :k]
    B = _bmm(W, Vk)
    C = jnp.swapaxes(Vk, 1, 2)
    return sig, B, C


def _tail_spectrum(sig_l: jax.Array, tail_energy: jax.Array,
                   n_tail: int) -> jax.Array:
    """Synthetic spectrum for the n_tail singular values an rsvd sketch
    never saw: geometric decay ``σ²_{l+j} = σ²_l ρ^j`` continuing from
    the last estimated value, with ρ bisected per batch member so the
    tail sums to the (exactly known) truncated energy, then renormalized
    so the energy identity holds to roundoff. Degenerate cases (σ_l = 0,
    ρ → 1, zero tail) all collapse to a flat tail with the right energy
    via the renormalization. The final clamp at σ²_l keeps the full
    spectrum NON-INCREASING (the allocators' ordering invariant) even
    when the truncated energy exceeds ``n_tail·σ²_l`` — i.e. when the
    sketch underestimated σ_l itself — at the cost of undercounting
    energy in exactly that saturated regime: ordering beats exactness
    there. Returns (b, n_tail) singular values."""
    s2 = jnp.maximum(sig_l.astype(jnp.float32) ** 2, 1e-30)     # (b,)
    x = tail_energy / s2                    # target Σρ^j in [0, n_tail]
    lo = jnp.zeros_like(x)
    hi = jnp.ones_like(x)
    for _ in range(30):                     # fp32 bisection on (0, 1)
        mid = 0.5 * (lo + hi)
        f = mid * (1.0 - mid ** n_tail) / (1.0 - mid + 1e-12)
        below = f < x
        lo = jnp.where(below, mid, lo)
        hi = jnp.where(below, hi, mid)
    rho = 0.5 * (lo + hi)
    j = jnp.arange(1, n_tail + 1, dtype=jnp.float32)
    t = s2[:, None] * rho[:, None] ** j                         # (b, n)
    t = t * (tail_energy / jnp.maximum(t.sum(axis=1), 1e-30))[:, None]
    return jnp.sqrt(jnp.minimum(t, s2[:, None]))


def _dec_rsvd(W, L, sL, k, oversample, iters, seed):
    """Randomized range-finder decomposition. Only GEMMs touch the large
    dimensions; the eigh is (k+oversample)². The returned spectrum is the
    top-l estimate EXTENDED by a synthetic geometric tail carrying the
    exact truncated energy (trace identity): ``‖M‖²_F = Σσ²`` is a cheap
    elementwise reduce, so ``tail = ‖M‖²_F − Σ_top-l σ̂²`` distributed
    over the min(d1, n·d2) − l unseen slots (``_tail_spectrum``) keeps
    total energy — and hence effective-rank allocation — honest for rsvd
    buckets instead of silently dropping the tail (DESIGN.md §1.5)."""
    b, d1, nd2 = W.shape
    ell = min(k + oversample, d1, nd2)
    M = _whiten_big(W, L, sL)
    omega = jax.random.normal(jax.random.PRNGKey(seed), (b, nd2, ell),
                              dtype=jnp.float32)
    Q = _qr_q(_bmm(M, omega))
    for _ in range(iters):
        Q = _qr_q(_bmm(M, _tn_project(M, Q)))
    T = _tn_project(M, Q)                           # Mᵀ Q : (b, nd2, l)
    lam, Uh = _eigh_desc(_tn_project(T, T))
    sig = jnp.sqrt(jnp.clip(lam, 0.0))              # top-l spectrum
    n_tail = min(d1, nd2) - ell
    if n_tail > 0:
        total = jnp.sum(M * M, axis=(1, 2))         # Σ σ², exact
        captured = jnp.sum(jnp.clip(lam, 0.0), axis=1)
        tail = jnp.maximum(total - captured, 0.0)
        sig = jnp.concatenate(
            [sig, _tail_spectrum(sig[:, ell - 1], tail, n_tail)], axis=1)
    Uk = _bmm(Q, Uh[:, :, :k])
    sigk = sig[:, :k]
    C = jnp.swapaxes(_bmm(T, Uh[:, :, :k]), 1, 2) \
        * (1.0 / jnp.maximum(sigk, 1e-20))[:, :, None]
    if L is not None:
        B = _solve_lower_t(L, Uk * sigk[:, None, :])
    elif sL is not None:
        B = (Uk * sigk[:, None, :]) / sL[:, :, None]
    else:
        B = Uk * sigk[:, None, :]
    return sig, B, C


@_highest
def decompose(W: jax.Array, *, gram: Optional[jax.Array] = None,
              factor: Optional[jax.Array] = None,
              diag: Optional[jax.Array] = None,
              k: int, damp: float = 1e-6, rsvd: int = 0,
              rsvd_oversample: int = 8, rsvd_iters: int = 2,
              rsvd_seed: int = 0
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched whitened rank-k decomposition of ``W (b, d1, n·d2)``.

    Whitener: ``gram`` (b, d1, d1) → damped Cholesky on device; ``factor``
    (b, d1, d1) upper-triangular R with RᵀR = G (streaming whitening,
    skips the Cholesky); ``diag`` (b, d1) scale; none → identity.

    Returns ``(sig, B, C)`` with ``W ≈ B @ C`` at rank k in the ORIGINAL
    space, B (b, d1, k), C (b, k, n·d2), and sig the full whitened
    spectrum. With ``rsvd > 0`` only the top-(k+oversample) entries are
    estimated individually; the rest are a synthetic geometric-decay tail
    holding the exact truncated energy (``_tail_spectrum``), so
    allocation metrics built on Σσ² (effective rank, energy shares) stay
    calibrated.
    """
    assert sum(x is not None for x in (gram, factor, diag)) <= 1
    W = jnp.asarray(W).astype(jnp.float32)
    L = sL = None
    if gram is not None:
        L, _ = cholesky_escalate(jnp.asarray(gram), damp)
    elif factor is not None:
        L = jnp.swapaxes(_fix_factor(jnp.asarray(factor)), -1, -2)
    elif diag is not None:
        sL = jnp.asarray(diag).astype(jnp.float32)
    k = int(min(k, W.shape[-1], W.shape[-2]))
    if rsvd:
        return _dec_rsvd(W, L, sL, k, int(rsvd_oversample),
                         int(rsvd_iters), int(rsvd_seed))
    if W.shape[-2] <= W.shape[-1]:
        return _dec_left(W, L, sL, k)
    return _dec_right(W, L, sL, k)


# ---------------------------------------------------------------------------
# Batched refine solve: C* = (BᵀGB)⁻¹ BᵀGW
# ---------------------------------------------------------------------------
@jax.jit
def _refine_normal_eqs(L2, B, eps):
    """FᵀF and the damped BᵀGB from F = L₂ᵀB (SPD by construction)."""
    F = jnp.einsum("bji,bjk->bik", L2, B)
    BtGB = jnp.einsum("bdi,bdj->bij", F, F)
    k = B.shape[-1]
    tr = jnp.trace(BtGB, axis1=-2, axis2=-1) / max(1, k)
    BtGB = BtGB + (eps * jnp.maximum(tr, 1e-12))[:, None, None] \
        * jnp.eye(k, dtype=jnp.float32)
    return F, BtGB


@_highest
def refine_solve(B: jax.Array, G: Optional[jax.Array], W: jax.Array,
                 eps: float = 1e-8,
                 factor: Optional[jax.Array] = None) -> jax.Array:
    """Batched closed-form coefficient update against a NEW Gram G
    (the refine pass re-captures G through the compressed model):

        C* = argmin_C ‖X(W − BC)‖_F = (BᵀGB + εI)⁻¹ BᵀGW.

    Factoring G = L₂L₂ᵀ once turns BᵀGB into FᵀF with F = L₂ᵀB and
    BᵀGW into (L₂ D)ᵀ W after the small solve D = (BᵀGB)⁻¹Fᵀ, so every
    large-dimension op is a GEMM and the solves are k×k / k×d only.
    B (b, d, k), G (b, d, d), W (b, d, m) → C (b, k, m).

    ``factor`` (upper-triangular R, RᵀR = G — the streaming-whitening
    form) replaces ``G``: L₂ = Rᵀ directly, so a whiten-streamed refine
    never materializes the Gram at all.
    """
    assert (G is None) != (factor is None)
    B = jnp.asarray(B).astype(jnp.float32)
    W = jnp.asarray(W).astype(jnp.float32)
    if factor is not None:
        L2 = jnp.swapaxes(_fix_factor(jnp.asarray(factor)), -1, -2)
    else:
        L2, _ = cholesky_escalate(jnp.asarray(G), 1e-9)
    F, BtGB = _refine_normal_eqs(L2, B, eps)
    Lk = jnp.linalg.cholesky(BtGB)
    D = _cho_solve(Lk, jnp.swapaxes(F, 1, 2))       # (b, k, d) — small RHS
    Et = _bmm(L2, jnp.swapaxes(D, 1, 2))            # L₂ Dᵀ : (b, d, k)
    return _tn_project(Et, W)                       # Etᵀ W = C*
