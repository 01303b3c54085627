"""Public jit'd wrappers for the Pallas kernels.

TPU backends run the compiled kernels. Off TPU, the CONSENSUS wrappers
(``consensus_mix``/``flat_consensus``/``flat_mix``) lower to the
equivalent XLA form instead: Pallas interpret mode executes the kernel
body op-by-op through Python/XLA and is ~10x slower than the einsum it
replaces (BENCH ``consensus_mix_kernel_r2048``: 0.9 vs 7.8 MB/ms), so
the kernel is NEVER auto-selected in interpret mode — interpret runs
only when a caller forces it (``force_kernel=True``, used by the
kernel-vs-XLA correctness tests and the kernel micro-bench rows).
Higher layers call these, never pallas_call directly.
"""
from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import consensus_mix as _cm
from repro.kernels import cnd_sketch as _cs
from repro.kernels import expert_gmm as _eg
from repro.kernels import flash_attention as _fa
from repro.kernels import robust_agg as _ra
from repro.kernels import rwkv6_scan as _rs
from repro.kernels import sparse_mix as _sm


def use_pallas() -> bool:
    """Whether the consensus wrappers dispatch to the Pallas kernels:
    compiled-backend only — interpret mode is for explicit correctness
    checks, never a default execution path."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# a Pallas TPU kernel in compiled HLO text: its pallas_call ``name``,
# numbered by XLA, on a custom call to the TPU kernel target
_KERNEL_CALL = re.compile(
    r"%([A-Za-z_][A-Za-z_0-9]*?)(?:\.\d+)* = [^\n]*"
    r'custom_call_target="tpu_custom_call"')


def pallas_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels a program compiled for the TPU calls
    (``jax.jit(f).lower(...).compile().as_text()``): what shows that a
    kernel, and not an XLA fallback, runs."""
    return set(_KERNEL_CALL.findall(hlo_text))


def grouped_matmul(lhs, rhs, sizes, precision=None):
    """Grouped matmul over held experts (``kernels.expert_gmm``): the
    Pallas kernels on the TPU, else the same arithmetic in XLA."""
    if use_pallas():
        return _eg.grouped_matmul(lhs, rhs, sizes, precision=precision)
    return _eg.grouped_matmul_xla(lhs, rhs, sizes, precision)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


@partial(jax.jit, static_argnames=("num_hashes", "m", "block_items",
                                   "force_kernel"))
def cnd_bitmaps(items, num_hashes: int = 3, m: int = 8192,
                block_items: int = 256, force_kernel: bool = False):
    """CND bitmap build (paper Alg. 1 lines 1-5): Pallas one-hot
    compare/any kernel on TPU; off TPU the scatter-based
    ``repro.core.sketch.build_bitmaps`` oracle (identical output), never
    the interpreted kernel."""
    if use_pallas() or force_kernel:
        return _cs.cnd_bitmaps(items, num_hashes, m,
                               block_items=block_items,
                               interpret=_interpret())
    from repro.core import sketch
    return sketch.build_bitmaps(items, num_hashes, m)


@partial(jax.jit, static_argnames=("force_kernel",))
def cnd_popcount(bitmaps, force_kernel: bool = False):
    """Per-bitmap set-bit counts: Pallas SWAR kernel on TPU, the
    ``repro.core.sketch.set_bits`` XLA form elsewhere."""
    if use_pallas() or force_kernel:
        return _cs.cnd_popcount(bitmaps, interpret=_interpret())
    from repro.core import sketch
    return sketch.set_bits(bitmaps)


@partial(jax.jit, static_argnames=("block_rows", "force_kernel"))
def consensus_mix(w, neighbors, eta, gamma, block_rows: int = 256,
                  force_kernel: bool = False):
    if use_pallas() or force_kernel:
        return _cm.consensus_mix(w, neighbors, eta, gamma,
                                 block_rows=block_rows,
                                 interpret=_interpret())
    from repro.kernels import ref
    return ref.consensus_mix(w, neighbors, eta, gamma)


def _mix_cols(k: int, p: int) -> int:
    """Columns of one slab of a flat (K, P) mix: the widest multiple of
    128 whose f32 block, its K rows padded to 8 sublanes, holds at most
    1 MiB, and never more than P (a full-width block is legal; the
    kernel masks a partial last slab). Every grid step costs a fixed
    overhead, so the fewer the better: at K = 4 the paper MLP's
    P = 23,936 mixes in one step, and a long buffer at K <= 8 in
    32,768-column slabs (P = 267,211,008 took 488 ms a mix on a v5e in
    128-column slabs, 19.8 ms in these). A bf16 wire block pads to 16
    sublanes of 2 bytes, the same bytes."""
    rows = -(-k // 8) * 8
    return min(p, 128 * max(1, (1 << 20) // (4 * rows * 128)))


@partial(jax.jit, static_argnames=("force_kernel",))
def flat_consensus(matrix, buf, force_kernel: bool = False):
    """A @ BUF over the flat (K, P) parameter buffer in one kernel launch
    (P is already lane-padded by repro.core.flatten); XLA matmul off
    TPU."""
    if use_pallas() or force_kernel:
        return _cm.flat_consensus(matrix, buf,
                                  block_cols=_mix_cols(*buf.shape),
                                  interpret=_interpret())
    from repro.core import flatten
    return flatten.matmul_nodes(matrix, buf)


@partial(jax.jit, static_argnames=("force_kernel",))
def flat_mix(eta, master, wire, gamma, wire_self=None,
             force_kernel: bool = False):
    """Fused eq.5 delta mix on the flat buffer (one kernel launch):
    OUT = MASTER + gamma * (ETA @ WIRE - rowsum(ETA) * WIRE_SELF).
    ``wire`` is the exchanged representation (master, a bf16 cast, or a
    stale gossip snapshot); ``wire_self`` (default ``wire``) the self
    payload; accumulation is always f32. Off TPU this is the equivalent
    XLA delta form, not the interpreted kernel."""
    if use_pallas() or force_kernel:
        return _cm.flat_mix(eta, master, wire, gamma, wire_self,
                            block_cols=_mix_cols(*master.shape),
                            interpret=_interpret())
    # one source of truth for the XLA delta form: flatten.mix_flat
    from repro.core import flatten
    return flatten.mix_flat(master, eta, gamma, use_kernel=False,
                            wire=wire, wire_self=wire_self)


@partial(jax.jit, static_argnames=("force_kernel",))
def sparse_mix(idx, val, master, wire, gamma, wire_self=None,
               force_kernel: bool = False):
    """Top-D sparse eq.5 delta mix on the flat buffer (one gather-mix
    kernel launch): OUT = MASTER + gamma * (gather-sum(VAL, WIRE[IDX])
    - rowsum(VAL) * WIRE_SELF), ``wire_self`` defaulting to ``wire``.
    O(K*D*P) instead of the dense O(K^2*P). Off TPU this is the XLA
    ``take`` + ``einsum`` delta form, not the interpreted kernel."""
    wself = wire if wire_self is None else wire_self
    if use_pallas() or force_kernel:
        return _sm.sparse_mix(idx, val, master, wself, wire, gamma,
                              interpret=_interpret())
    # one source of truth for the XLA form: flatten.sparse_mix_flat
    from repro.core import flatten
    return flatten.sparse_mix_flat(master, idx, val, gamma,
                                   use_kernel=False, wire=wire,
                                   wire_self=wself)


@partial(jax.jit, static_argnames=("force_kernel",))
def cluster_mix(idx, val, master, wself, wire, gamma_node,
                force_kernel: bool = False):
    """Block-diagonal cluster eq.5 delta mix with a PER-NODE gamma (the
    intra-cluster tier of hierarchical consensus): OUT = MASTER +
    g[:, None] * (gather-sum(VAL, WIRE[IDX]) - rowsum(VAL) * WSELF).
    The index table only lists co-cluster members, so each cluster mixes
    at its own stability bound. Off TPU this is the XLA gather-axpy
    delta form, not the interpreted kernel."""
    if use_pallas() or force_kernel:
        return _sm.cluster_mix(idx, val, master, wself, wire, gamma_node,
                               interpret=_interpret())
    # one source of truth for the XLA form: flatten.cluster_mix_flat
    from repro.core import flatten
    return flatten.cluster_mix_flat(master, idx, val, gamma_node,
                                    use_kernel=False, wire=wire,
                                    wire_self=wself)


@partial(jax.jit, static_argnames=("force_kernel",))
def robust_agg(weights, mask, buf, sent, force_kernel: bool = False):
    """Coordinate-wise robust neighbor aggregation (trimmed-mean /
    median position weights) over the flat (K, P) buffer: the
    Byzantine-robust replacement for the eq. 5 mix. Pallas row-reduction
    kernel on TPU, sort-based XLA fallback elsewhere."""
    if use_pallas() or force_kernel:
        block_cols = 512 if buf.shape[1] % 512 == 0 else 128
        return _ra.robust_agg(weights, mask, buf, sent,
                              block_cols=block_cols, interpret=_interpret())
    return _ra.robust_agg_xla(weights, mask, buf, sent)


def consensus_mix_pytree(params, neighbor_params, eta, gamma):
    """Apply the fused mix to a whole param pytree at once.

    params: leaves (...); neighbor_params: leaves (N, ...). The pytree is
    packed into ONE flat (N+1, P) buffer (self in row 0) and mixed with a
    single fused op — no per-leaf dispatch, no per-leaf tile padding (the
    seed path padded every leaf to 32K-element tiles, catastrophic for
    bias-sized leaves)."""
    from repro.core import flatten

    stacked = jax.tree.map(
        lambda w, nb: jnp.concatenate(
            [w[None], nb], dtype=jnp.promote_types(w.dtype, nb.dtype)),
        params, neighbor_params)
    buf, layout = flatten.flatten(stacked)
    n = buf.shape[0] - 1
    eta_full = jnp.zeros((n + 1, n + 1), jnp.float32)
    eta_full = eta_full.at[0, 1:].set(eta.astype(jnp.float32))
    out = flatten.mix_flat(buf, eta_full, gamma)
    mixed = flatten.unflatten(out, layout)
    return jax.tree.map(lambda m, w: m[0].astype(w.dtype), mixed, params)


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, chunk: int = 32):
    return _rs.rwkv6_scan(r, k, v, w, u, chunk=chunk,
                          interpret=_interpret())
