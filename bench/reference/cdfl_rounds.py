"""Plain reference of C-DFL rounds (arXiv:2209.10722), for any model.

Written from the paper and the configuration, in NumPy and plain
``jax.numpy``, and importing nothing of the system under test. The model
is an argument: a plain model names its leaves (``LEAVES``, sorted, as a
dict pytree flattens) and the node-stacked arrays its minibatches are
gathered from (``BATCH``), and gives ``init(key, config, dtype)`` and
``loss(params, batch)``. One round, for every node k of every variant:

1. what k puts on the wire: its buffer, or (a straggler) last round's;
2. eq. 5 consensus: ``W_k + gamma * (sum_i eta_ki sent_i - rowsum_k W_k)``
   with the eq. 6 CND weights of the round's graph (the paper's ring,
   kept dense or cut to its top D; a platoon's radio-range graph; a
   Manhattan trace's radio-range graph cut to the D nearest links),
   fault masks and the streaming-redundancy column reweight;
3. ``local_steps`` Adam steps (paper eq. 8) on the model's loss, over
   minibatches drawn with the round's key;
4. a crashed node keeps its round-entry parameters and moments.

Schedules that the configuration draws from seeds (mobility traces, fault
plans, the redundancy plan, the hashes of the sketches) are regenerated
here from the same seeds, so the reference needs only the inputs the
benchmark made: the fleet data, the CND feature tokens and the seeds.
``run(..., dtype=jnp.bfloat16)`` computes the same rounds in bfloat16:
the control that the comparison has to reject. A model's own reference
module binds it: ``run = partial(cdfl_rounds.run, model)``.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Hashing (the CND bitmaps of paper Alg. 1 and the HyperLogLog registers).
# ---------------------------------------------------------------------------

_PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)


def mix32(x: np.ndarray, seed: int) -> np.ndarray:
    """32-bit avalanche hash, wrapping uint32 arithmetic."""
    x = np.asarray(x).astype(np.uint32)
    x = x ^ np.uint32((seed * 0x9E3779B9 + 0x7F4A7C15) & 0xFFFFFFFF)
    x = x * np.uint32(_PRIMES[seed % len(_PRIMES)])
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x85EBCA77)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE3D)
    return x ^ (x >> np.uint32(16))


def cnd_ratios(items: np.ndarray, hashes: int, bits: int) -> np.ndarray:
    """Per node: distinct buckets hit by its items, averaged over the
    hash functions (Alg. 1 line 9), over its item count."""
    k, n, f = items.shape
    counts = np.zeros((k, hashes), np.float32)
    for s in range(hashes):
        h = np.zeros((k, n), np.uint32)
        for j in range(f):
            h = mix32(h * np.uint32(31) + items[..., j].astype(np.uint32),
                      s + j)
        bucket = mix32(h, 101 + s) % np.uint32(bits)
        for node in range(k):
            counts[node, s] = len(np.unique(bucket[node]))
    est = np.minimum(counts.mean(axis=1, dtype=np.float32), bits)
    return np.clip(est / np.float32(n), 1e-6, 1.0).astype(np.float32)


def hll_tables(item_ids: np.ndarray, registers: int):
    """Register index and rank (leading-zero run + 1) of every slot."""
    log2m = registers.bit_length() - 1
    h = mix32(item_ids, 131)
    reg = (h & np.uint32(registers - 1)).astype(np.int32)
    tail = (h >> np.uint32(log2m)).astype(np.float64)
    bitlen = np.where(tail > 0, np.floor(np.log2(np.maximum(tail, 1))) + 1,
                      0)
    rho = (32 - bitlen - log2m + 1).astype(np.int32)
    return reg, rho


def hll_estimate(regs: jax.Array) -> jax.Array:
    """(K, M) registers -> (K,) distinct estimate (Flajolet et al. 2007,
    small-range linear counting, no large-range correction)."""
    m = regs.shape[-1]
    alpha = (0.7213 / (1.0 + 1.079 / m) if m >= 128 else
             0.709 if m >= 64 else 0.697 if m >= 32 else 0.673)
    raw = jnp.float32(alpha * m * m) / jnp.exp2(
        -regs.astype(jnp.float32)).sum(axis=-1)
    zeros = (regs == 0).sum(axis=-1).astype(jnp.float32)
    small = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    return jnp.where((raw <= 2.5 * m) & (zeros > 0), small, raw)


# ---------------------------------------------------------------------------
# Seeded host schedules.
# ---------------------------------------------------------------------------

def _rng(seed: int, tag: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), zlib.crc32(tag.encode())]))


_HEADING = np.asarray([[1.0, 0.0], [-1.0, 0.0], [0.0, 1.0], [0.0, -1.0]])


def manhattan_positions(rounds, k, *, speed, area, dt, seed, block=200.0,
                        turn_prob=0.5):
    """Vehicles start at random grid intersections and drive the streets,
    turning (never back) with ``turn_prob`` at each intersection they
    cross; positions wrap around the ``area`` torus."""
    rng = _rng(seed, "manhattan")
    grid = max(int(area // block), 1)
    p = block * rng.integers(0, grid, size=(k, 2)).astype(np.float64)
    head = rng.integers(0, 4, size=k)
    out = np.empty((rounds, k, 2), np.float32)
    for r in range(rounds):
        out[r] = p
        for i in range(k):
            axis = 0 if _HEADING[head[i]][0] != 0 else 1
            gap = block - np.mod(p[i, axis], block)
            left = speed * dt
            while left > 0:
                d = min(left, gap)
                p[i] += _HEADING[head[i]] * d
                left -= d
                gap -= d
                if gap <= 1e-9:
                    gap = block
                    if rng.random() < turn_prob:
                        head[i] = rng.choice([2, 3] if head[i] < 2
                                             else [0, 1])
        p = np.mod(p, area)
    return out


def platoon_positions(rounds, k, *, speed, speed_jitter, dt, seed,
                      headway=100.0, lanes=2, lane_gap=4.0):
    """Vehicles ``headway`` apart on a road, each at its own constant
    speed ~ N(speed, jitter * speed), no slower than a tenth of it."""
    rng = _rng(seed, "platoon")
    v = np.maximum(speed * (1.0 + speed_jitter * rng.standard_normal(k)),
                   0.1 * speed)
    t = dt * np.arange(rounds, dtype=np.float64)
    out = np.empty((rounds, k, 2), np.float32)
    out[..., 0] = -headway * np.arange(k) + t[:, None] * v[None, :]
    out[..., 1] = lane_gap * (np.arange(k) % lanes)
    return out


def _distances(pos: np.ndarray) -> np.ndarray:
    """Pairwise distances of float32 positions: the difference in float32,
    its square in float64."""
    delta = (pos[:, None, :] - pos[None, :, :]).astype(np.float64)
    return np.sqrt((delta ** 2).sum(-1))


def _cnd_rows(w: np.ndarray, ratios: np.ndarray) -> np.ndarray:
    """Eq. 6: neighbor i of k weighs in proportion to its distinct ratio,
    rows normalized over the neighborhood; an empty row stays empty."""
    w = w * ratios[None, :]
    s = w.sum(axis=1, keepdims=True)
    return np.where(s > 0, w / np.maximum(s, 1e-12), 0.0).astype(np.float32)


def _top(eta: np.ndarray, degree: int) -> np.ndarray:
    """Keep each row's ``degree`` largest weights at the row's mass."""
    keep = np.argsort(-eta, axis=1, kind="stable")[:, :degree]
    kept = np.zeros_like(eta)
    np.put_along_axis(kept, keep, np.take_along_axis(eta, keep, 1), 1)
    s = kept.sum(axis=1, keepdims=True)
    return np.where(s > 0, kept * (eta.sum(1, keepdims=True) /
                                   np.maximum(s, 1e-12)), 0.0)


def ring(k: int) -> np.ndarray:
    a = np.zeros((k, k), np.float32)
    for i in range(k):
        j = (i + 1) % k
        if i != j:
            a[i, j] = a[j, i] = 1.0
    return a


def mixing_schedule(fleet, mobility, rounds, ratios):
    """(R, K, K) eta of every round before faults, and the (R,) gamma."""
    k, cap = fleet["num_nodes"], fleet["gamma"]
    if mobility is None:
        eta = _cnd_rows(ring(k), ratios)
        if fleet["mixing_format"] == "sparse":
            eta = _top(eta, fleet["degree"])
        etas = np.broadcast_to(eta, (rounds, k, k)).copy()
    elif mobility["kind"] == "manhattan":
        pos = manhattan_positions(rounds, k, speed=mobility["speed"],
                                  area=mobility.get("area", 1000.0),
                                  dt=mobility.get("dt", 1.0),
                                  seed=mobility["seed"])
        etas = np.zeros((rounds, k, k), np.float32)
        d_keep = fleet["degree"]
        for r in range(rounds):
            d = _distances(pos[r])
            link = (d <= mobility["radio_range"]).astype(np.float32)
            np.fill_diagonal(link, 0.0)
            # the D nearest live links; ties go the way argpartition
            # breaks them, as in any top-D selection on equal distances
            score = np.where(link > 0, -d, -np.inf)
            near = np.argpartition(score, -d_keep, axis=1)[:, -d_keep:]
            w = np.zeros((k, k), np.float32)
            np.put_along_axis(w, near, np.take_along_axis(link, near, 1), 1)
            etas[r] = _cnd_rows(w, ratios)
    elif mobility["kind"] == "platoon":
        pos = platoon_positions(rounds, k, speed=mobility["speed"],
                                speed_jitter=mobility["speed_jitter"],
                                dt=mobility["dt"], seed=mobility["seed"])
        etas = np.zeros((rounds, k, k), np.float32)
        for r in range(rounds):
            link = (_distances(pos[r]) <= mobility.get("radio_range", 250.0)
                    ).astype(np.float32)
            np.fill_diagonal(link, 0.0)
            etas[r] = _cnd_rows(link, ratios)
    else:
        raise ValueError(f"no reference for mobility {mobility['kind']!r}")
    gammas = np.minimum(cap, 0.99 / np.maximum(
        etas.sum(axis=2).max(axis=1), 1e-6)).astype(np.float32)
    return etas, gammas


def fault_schedule(faults, rounds, k):
    """Link mask (R, K, K), health (R, K) and straggle (R, K)."""
    mask = np.ones((rounds, k, k), np.float32)
    health = np.ones((rounds, k), np.float32)
    straggle = np.zeros((rounds, k), np.float32)
    for kind in faults["kinds"]:
        rng = _rng(faults["seed"], kind)
        if kind == "link_drop":
            drop = rng.random((rounds, k, k)) < faults["drop_rate"]
            drop |= np.swapaxes(drop, 1, 2)
            mask *= ~drop
        elif kind == "crash":
            u = rng.random((rounds, k))
            alive = np.ones(k, bool)
            for r in range(rounds):
                alive = ((alive & ~(u[r] < faults["crash_rate"]))
                         | (~alive & (u[r] < faults.get("recover_rate",
                                                        0.3))))
                health[r] = alive
        elif kind == "straggle":
            straggle = np.maximum(
                straggle, rng.random((rounds, k)) < faults["straggle_rate"])
        else:
            raise ValueError(f"no reference for fault kind {kind!r}")
    mask = mask * health[:, :, None] * health[:, None, :]
    return mask, health, (straggle * health).astype(np.float32)


def masked(etas: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Drop the masked links, rescaling each row to its former mass."""
    kept = etas * mask
    s = kept.sum(axis=2, keepdims=True)
    target = etas.sum(axis=2, keepdims=True)
    return np.where(s > 0, kept * (target / np.maximum(s, 1e-12)),
                    0.0).astype(np.float32)


def duplicate_heavy(ingest, k, n):
    """Slot -> source slot of every node: the second half of the fleet
    keeps a pool of (1 - fraction) n distinct items and fills the rest
    with copies drawn from it."""
    rng = _rng(ingest["seed"], "duplicate_heavy")
    slots = np.tile(np.arange(n, dtype=np.int32), (k, 1))
    pool = max(1, int(round((1.0 - ingest["duplicate_fraction"]) * n)))
    for node in range(k // 2, k):
        dup = rng.integers(0, pool, size=max(0, n - pool))
        slots[node] = np.concatenate([np.arange(pool), dup])
    return slots


# ---------------------------------------------------------------------------
# The rounds.
# ---------------------------------------------------------------------------

def _round(loss_fn, train, shape, with_faults, reweight_gate, guard, plant,
           state, inputs):
    """One round of one variant; every array has the node axis first."""
    params, m, v, step, prev, hll = state
    (eta, gamma, r, key, data, regs, rhos, health, straggle) = inputs
    dt = eta.dtype
    idx = jax.random.randint(jax.random.fold_in(key, r), shape, 0,
                             jax.tree.leaves(data)[0].shape[1])
    if plant == "no_exchange":
        gamma = jnp.zeros_like(gamma)
    if reweight_gate is not None:
        flat = idx.reshape(idx.shape[0], -1)
        hll = jax.vmap(lambda h, g, q, i: h.at[g[i]].max(q[i]))(
            hll, regs, rhos, flat)
        est = jnp.maximum(hll_estimate(hll), 1.0)
        spread = est.max() / jnp.maximum(est.min(), 1e-6)
        scaled = eta * (est / est.mean()).astype(dt)[None, :]
        s = scaled.sum(axis=1, keepdims=True)
        scaled = jnp.where(s > 0, scaled * (eta.sum(1, keepdims=True) /
                                            jnp.maximum(s, 1e-12)), 0)
        eta = jnp.where(spread > reweight_gate, scaled, eta)
    entry = params
    sent = params
    if with_faults:
        sent = jax.tree.map(
            lambda a, b: jnp.where(
                straggle.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, b, a),
            params, prev)
        # the receivers drop a payload that is not finite or exceeds the
        # guard's threshold: its column goes, rows keep their mass, and
        # the sender's own buffer stands in for it
        rows = [a.reshape(a.shape[0], -1) for a in jax.tree.leaves(sent)]
        bad = ~jnp.stack([jnp.isfinite(a).all(1) for a in rows]).all(0)
        if guard > 0:
            bad |= jnp.stack([jnp.nan_to_num(jnp.abs(a), nan=jnp.inf)
                              .max(1) > guard for a in rows]).any(0)
        kept = eta * (~bad).astype(dt)[None, :]
        s = kept.sum(axis=1, keepdims=True)
        eta = jnp.where(bad.any(), jnp.where(
            s > 0, kept * (eta.sum(1, keepdims=True)
                           / jnp.maximum(s, 1e-12)), 0), eta)
        sent = jax.tree.map(
            lambda a, b: jnp.where(
                bad.reshape((-1,) + (1,) * (a.ndim - 1)), b, a),
            sent, params)

    def mix(w, s):
        flat_w, flat_s = w.reshape(w.shape[0], -1), s.reshape(s.shape[0], -1)
        out = flat_w + gamma * (eta @ flat_s
                                - eta.sum(axis=1)[:, None] * flat_w)
        return out.reshape(w.shape).astype(dt)

    params = jax.tree.map(mix, params, sent)

    def node(p, m, v, t, dk, ik):
        def one(carry, i):
            p, m, v, t = carry
            loss, g = jax.value_and_grad(loss_fn)(
                p, jax.tree.map(lambda a: a[i], dk))
            t = t + 1
            tf = t.astype(jnp.float32)
            corr = (jnp.sqrt(1 - train["beta2"] ** tf)
                    / (1 - train["beta1"] ** tf)).astype(dt)
            b1, b2 = train["beta1"], train["beta2"]
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            p = jax.tree.map(
                lambda a, b, c: a - train["learning_rate"] * corr * b
                / (jnp.sqrt(c) + train["eps"]), p, m, v)
            return (p, m, v, t), loss
        (p, m, v, t), losses = jax.lax.scan(one, (p, m, v, t), ik)
        return p, m, v, t, losses.astype(jnp.float32).mean()

    train_idx = idx[..., :shape[-1] // 2] if plant == "half_batch" else idx
    new, m2, v2, step2, loss = jax.vmap(node)(params, m, v, step, data,
                                              train_idx)
    if with_faults:
        finite = jnp.stack([jnp.isfinite(a.reshape(a.shape[0], -1)).all(1)
                            for a in jax.tree.leaves(new)]).all(0)
        keep = (health > 0) & finite

        def pick(a, b):
            return jnp.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
        new = jax.tree.map(pick, new, entry)
        m2, v2 = jax.tree.map(pick, m2, m), jax.tree.map(pick, v2, v)
        step2 = jnp.where(keep, step2, step)
    return (new, m2, v2, step2, entry, hll), loss


def _norms(tree, leaves):
    """(V, K, leaves) Euclidean norm of every node's every leaf."""
    return jnp.stack([jnp.sqrt(jnp.sum(
        jnp.square(tree[name].astype(jnp.float32)).reshape(
            tree[name].shape[:2] + (-1,)), axis=-1)) for name in leaves],
        axis=-1)


def run(model, config: dict, traffic: dict, inputs: dict, calls: int,
        dtype=jnp.float32, plant: str | None = None) -> dict:
    """The first ``calls`` entry calls of a cell, in the reference, with
    the plain ``model``.

    ``inputs``: the (K, N, ...) fleet arrays that ``model.BATCH`` names
    and ``items`` (K, N, F) CND feature tokens as the benchmark made them,
    and
    ``variants``: one dict per variant with ``init_seed``,
    ``sample_seed`` and ``mobility`` (None or the scenario with its
    seed), plus ``faults`` and ``ingest`` with their seeds or None.

    Returns ``loss``, a list of (V, R, K) arrays, one per call; ``m``,
    the (V, K, leaves) norms of Adam's first moment after the first
    call; and ``change``, the norms of each leaf's change after the
    last call.

    ``plant`` breaks the rounds on purpose, to read what the comparison
    makes of a fault: ``"half_batch"`` trains on the first half of each
    minibatch, ``"no_exchange"`` leaves the consensus exchange out.
    """
    train, fleet = config["train"], config["fleet"]
    data = {name: inputs[name] for name in model.BATCH}
    k, n = jax.tree.leaves(data)[0].shape[:2]
    rpc = traffic["rounds_per_call"]
    total = calls * rpc
    items = np.asarray(inputs["items"])
    ratios = cnd_ratios(items, fleet["cnd_hashes"], fleet["cnd_bits"])
    faults, ingest = inputs.get("faults"), inputs.get("ingest")
    etas, gammas = [], []
    for var in inputs["variants"]:
        e, g = mixing_schedule(fleet, var["mobility"], total, ratios)
        etas.append(e)
        gammas.append(g)
    etas, gammas = np.stack(etas), np.stack(gammas)
    health = np.ones((total, k), np.float32)
    straggle = np.zeros((total, k), np.float32)
    if faults is not None:
        mask, health, straggle = fault_schedule(faults, total, k)
        etas = np.stack([masked(e, mask) for e in etas])
    regs = rhos = jnp.zeros((k, 1), jnp.int32)
    gate = None
    if ingest is not None:
        if (ingest["scenario"], ingest["weighting"]) != ("duplicate_heavy",
                                                         "mixing"):
            raise ValueError("the reference covers the duplicate_heavy "
                             "scenario reweighting the mixing only")
        slots = duplicate_heavy(ingest, k, n)
        data = jax.tree.map(lambda d: jax.vmap(lambda a, s: a[s])(
            d, jnp.asarray(slots)), data)
        ids = np.arange(k, dtype=np.int64)[:, None] * n + slots
        regs, rhos = (jnp.asarray(t) for t in
                      hll_tables(ids.astype(np.uint32),
                                 ingest["hll_registers"]))
        gate = ingest["spread_gate"]
    data = jax.tree.map(lambda a: a.astype(dtype) if jnp.issubdtype(
        a.dtype, jnp.floating) else a, data)
    shape = (k, fleet["local_steps"], train["batch_size"])

    def init(seed):
        p = model.init(jax.random.PRNGKey(seed), config, dtype)
        p = jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape), p)
        zeros = jax.tree.map(jnp.zeros_like, p)
        hll = jnp.zeros((k, ingest["hll_registers"] if ingest else 1),
                        jnp.int32)
        return (p, zeros, zeros, jnp.zeros((k,), jnp.int32), p, hll)

    states = [init(v["init_seed"]) for v in inputs["variants"]]
    state = jax.tree.map(lambda *a: jnp.stack(a), *states)
    p0 = state[0]
    keys = jnp.stack([jax.random.PRNGKey(v["sample_seed"])
                      for v in inputs["variants"]])
    body = partial(_round, model.loss, train, shape, faults is not None,
                   gate, faults["guard_threshold"] if faults else 0.0, plant)

    @jax.jit
    def one_round(st, e, g, r, hl, sg, keys, data, regs, rhos):
        return jax.vmap(lambda s_, e_, g_, key: body(
            s_, (e_, g_, r, key, data, regs, rhos, hl, sg)))(st, e, g, keys)

    out = {"loss": []}
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        for c in range(calls):
            losses = []
            for r in range(c * rpc, (c + 1) * rpc):
                state, loss = one_round(
                    state, jnp.asarray(etas[:, r], dtype),
                    jnp.asarray(gammas[:, r], dtype), r,
                    jnp.asarray(health[r]), jnp.asarray(straggle[r]),
                    keys, data, regs, rhos)
                losses.append(loss)
            out["loss"].append(np.stack([np.asarray(a) for a in losses],
                                        axis=1))
            if c == 0:
                out["m"] = np.asarray(_norms(state[1], model.LEAVES))
        change = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                              - b.astype(jnp.float32), state[0], p0)
        out["change"] = np.asarray(_norms(change, model.LEAVES))
    return out
