"""IVF approximate search: k-means coarse quantizer + probed-cluster scoring.

The paper's §VIII.F scalability pathway ("FAISS index build time, memory
footprint") — at 10⁶+ passages exact MIPS over everything stops being free,
so we implement FAISS-IVF's structure TPU-natively:

* k-means (Lloyd's, batched jnp) learns ``n_clusters`` centroids;
* each passage is assigned to its nearest centroid;
* a query scores only the ``n_probe`` nearest clusters' members.

Two scoring implementations, both cached fixed-shape jit closures:

* ``impl="bag"`` (default) — an ``embedding_bag``-style posting-list
  gather: cluster members live in one flat cluster-major array with
  ``(starts, lens)`` offsets, each query's candidate slots map onto its
  probed clusters' ranges via a cumulative-length segment lookup, and the
  gather width is the (power-of-two bucketed) sum of the ``n_probe``
  *largest* posting lists — so memory traffic scales with actual posting
  mass, not ``n_probe × max_bucket`` worst-case padding. Rows come back in
  **canonical order**: score descending, ties by ascending passage id
  (a lexicographic ``lax.sort`` — the same total order every other backend
  implements, and what makes sharded IVF merges bit-identical).
* ``impl="padded"`` — the static ``(n_probe × capacity)`` padded-bucket
  gather + masked MIPS, kept as the differential-testing oracle for the
  bag path (ties order probe-major here; tests compare on tie-free data).

Invalid slots (a probe set holding fewer than ``k`` members) carry the
sentinel ``(id=-1, score=-inf)``; :class:`~repro.retrieval.backend.
IVFBackend` narrows rows to the widest all-finite prefix.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import SCORE_PRECISION
from repro.retrieval.index import l2_normalize


def kmeans(
    x: jnp.ndarray, n_clusters: int, *, n_iters: int = 10, key: jax.Array | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Lloyd's k-means on the unit sphere. Returns (centroids, assignment)."""
    n, d = x.shape
    if n_clusters > n:
        raise ValueError(f"n_clusters={n_clusters} > n={n}")
    key = key if key is not None else jax.random.PRNGKey(0)
    init_idx = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent = x[init_idx]

    def step(cent, _):
        sim = x @ cent.T  # cosine: inputs are normalized
        assign = jnp.argmax(sim, axis=-1)
        onehot = jax.nn.one_hot(assign, n_clusters, dtype=x.dtype)  # (n, c)
        sums = onehot.T @ x  # (c, d)
        counts = onehot.sum(axis=0)[:, None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), cent)
        return l2_normalize(new), None

    cent, _ = jax.lax.scan(step, cent, None, length=n_iters)
    assign = jnp.argmax(x @ cent.T, axis=-1)
    return cent, assign


def _pow2_bucket(n: int, floor: int = 8) -> int:
    """Next power-of-two >= n (floored) — bounds the closure count."""
    cap = floor
    while cap < n:
        cap <<= 1
    return cap


@dataclasses.dataclass
class IVFIndex:
    centroids: jnp.ndarray  # (c, d)
    buckets: jnp.ndarray  # (c, cap) int32 passage ids, -1 padded
    bucket_mask: jnp.ndarray  # (c, cap) bool
    embeddings: jnp.ndarray  # (n, d) normalized

    @classmethod
    def build(
        cls,
        embeddings: jnp.ndarray,
        n_clusters: int,
        *,
        n_iters: int = 10,
        key: jax.Array | None = None,
    ) -> "IVFIndex":
        x = l2_normalize(jnp.asarray(embeddings, jnp.float32))
        cent, assign = kmeans(x, n_clusters, n_iters=n_iters, key=key)
        assign_np = np.asarray(assign)
        cap = max(int(np.bincount(assign_np, minlength=n_clusters).max()), 1)
        buckets = np.full((n_clusters, cap), -1, np.int32)
        fill = np.zeros((n_clusters,), np.int64)
        for pid, c in enumerate(assign_np):
            buckets[c, fill[c]] = pid
            fill[c] += 1
        b = jnp.asarray(buckets)
        return cls(cent, b, b >= 0, x)

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    # -- flat posting-list (bag) layout ---------------------------------------
    def _bag(self):
        """Lazy cluster-major flat member layout for the bag gather:
        ``(members (n,), member_embs (n, d), starts (c,), lens (c,))`` —
        the ``embedding_bag`` idiom (kernels/embedding_bag) applied to
        inverted lists. ``member_embs`` re-orders the corpus rows
        cluster-major once, so probing gathers contiguous-ish rows."""
        bag = getattr(self, "_bag_cache", None)
        if bag is None:
            mask = np.asarray(self.bucket_mask)
            buckets = np.asarray(self.buckets)
            lens = mask.sum(axis=1).astype(np.int32)
            members = buckets[mask].astype(np.int32)  # row-major = cluster-major
            starts = (np.cumsum(lens) - lens).astype(np.int32)
            bag = self._bag_cache = (
                jnp.asarray(members),
                self.embeddings[jnp.asarray(members)],
                jnp.asarray(starts),
                jnp.asarray(lens),
                lens,  # host copy for static width sizing
            )
        return bag

    def _bag_width(self, n_probe: int) -> int:
        """Static candidate width of the bag gather: the sum of the
        ``n_probe`` largest posting lists (no query can probe more members),
        power-of-two bucketed so the closure count stays logarithmic."""
        *_, lens_np = self._bag()
        top = np.sort(lens_np)[::-1][:n_probe]
        return _pow2_bucket(int(top.sum()))

    # -- cached search closures ------------------------------------------------
    def _search_fn(self, k: int, n_probe: int, impl: str = "bag"):
        """Cached ``(program, arrays)``: a jit-compiled fixed-shape
        ``(*arrays, (Q_BLOCK, d))`` probe+score program — one per (impl, k,
        n_probe), like ``DenseIndex._search_fn`` — and the index arrays it
        takes as arguments (never captured constants, which would be
        compiled into the program). The fixed block shape is what makes a
        query row's scores independent of the caller's batch size: XLA may
        tile a shape-(nq, d) matmul differently per nq, which perturbs the
        last float bits — enough to break the serving pipeline's bit-exact
        chunking parity for IVF-backed bundles."""
        cache = getattr(self, "_fn_cache", None)
        if cache is None:
            cache = self._fn_cache = {}
        key = (impl, k, n_probe)
        fn = cache.get(key)
        if fn is not None:
            return fn

        cap = self.buckets.shape[1]
        k_eff = min(k, n_probe * cap)

        if impl == "padded":

            arrays = (self.centroids, self.buckets, self.bucket_mask, self.embeddings)

            def core(centroids, buckets, bucket_mask, embeddings, q):
                q = l2_normalize(q)  # (Q_BLOCK, d) raw; normalized here
                _, probe = jax.lax.top_k(
                    jnp.matmul(q, centroids.T, precision=SCORE_PRECISION), n_probe
                )  # (bq, p)
                cand_ids = buckets[probe].reshape(q.shape[0], -1)  # (bq, p*cap)
                cand_mask = bucket_mask[probe].reshape(q.shape[0], -1)
                cand_vecs = embeddings[jnp.maximum(cand_ids, 0)]  # (bq, m, d)
                scores = jnp.einsum(
                    "qd,qmd->qm", q, cand_vecs, precision=SCORE_PRECISION
                )
                scores = jnp.where(cand_mask, scores, -jnp.inf)
                v, sel = jax.lax.top_k(scores, k_eff)
                ids = jnp.take_along_axis(cand_ids, sel, axis=-1)
                return v, ids

        elif impl == "bag":
            members, member_embs, starts, lens, _ = self._bag()
            w = self._bag_width(n_probe)
            arrays = (self.centroids, members, member_embs, starts, lens)

            def core(centroids, members, member_embs, starts, lens, q):
                q = l2_normalize(q)  # (Q_BLOCK, d) raw; normalized here
                _, probe = jax.lax.top_k(
                    jnp.matmul(q, centroids.T, precision=SCORE_PRECISION), n_probe
                )  # (bq, p)
                lens_p = lens[probe]  # (bq, p)
                ends = jnp.cumsum(lens_p, axis=1)
                j = jnp.arange(w, dtype=jnp.int32)[None, :]  # (1, w)
                # candidate slot j belongs to the first probe segment whose
                # cumulative end exceeds it (broadcast searchsorted)
                seg = (j[:, :, None] >= ends[:, None, :]).sum(-1)  # (bq, w)
                valid = seg < n_probe
                segc = jnp.minimum(seg, n_probe - 1)
                begins = ends - lens_p
                probe_sel = jnp.take_along_axis(probe, segc, axis=1)  # (bq, w)
                local = j - jnp.take_along_axis(begins, segc, axis=1)
                midx = jnp.where(valid, starts[probe_sel] + local, 0)
                scores = jnp.einsum(
                    "qd,qwd->qw", q, member_embs[midx], precision=SCORE_PRECISION
                )
                scores = jnp.where(valid, scores, -jnp.inf)
                ids = jnp.where(valid, members[midx], -1)
                if w < k_eff:  # tiny posting mass: pad up to the contract width
                    pad = k_eff - w
                    scores = jnp.concatenate(
                        [scores, jnp.full((scores.shape[0], pad), -jnp.inf)], axis=1
                    )
                    ids = jnp.concatenate(
                        [ids, jnp.full((ids.shape[0], pad), -1, jnp.int32)], axis=1
                    )
                # canonical row order: score descending, ties by ascending
                # passage id (lexicographic sort on (-score, id)) — the
                # protocol's total order, and shard-merge compatible
                neg, ids_sorted = jax.lax.sort((-scores, ids), num_keys=2)
                return -neg[:, :k_eff], ids_sorted[:, :k_eff]

        else:
            raise ValueError(f"unknown ivf impl {impl!r}; expected 'bag' or 'padded'")

        entry = cache[key] = (jax.jit(core), arrays)
        return entry

    def search_batch(
        self,
        query_vecs: jnp.ndarray,
        k: int,
        *,
        n_probe: int = 4,
        impl: str = "bag",
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Probed approximate search. Returns (scores, ids), (nq, k_eff).

        Queries run through a cached compiled closure in fixed ``Q_BLOCK``
        chunks (zero-padded), so each row's result is bit-identical whether
        it arrives alone or inside any batch — the same contract as
        ``DenseIndex.search_batch``, and what the serving layer's
        mixed-backend parity tests pin. ``impl`` selects the bag gather
        (default) or the padded-bucket oracle (module docstring)."""
        from repro.retrieval.index import Q_BLOCK

        q = np.asarray(query_vecs, np.float32)
        nq = q.shape[0]
        n_probe = min(n_probe, self.n_clusters)
        cap = self.buckets.shape[1]
        k_eff = min(k, n_probe * cap)
        if nq == 0:
            return jnp.zeros((0, k_eff), jnp.float32), jnp.zeros((0, k_eff), jnp.int32)
        fn, arrays = self._search_fn(k, n_probe, impl)
        pad = (-nq) % Q_BLOCK
        if pad:
            q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)], axis=0)
        vals, ids = [], []
        for s in range(0, q.shape[0], Q_BLOCK):
            v, i = fn(*arrays, jnp.asarray(q[s : s + Q_BLOCK]))
            vals.append(np.asarray(v, np.float32))
            ids.append(np.asarray(i, np.int32))
        v_np = np.concatenate(vals, axis=0)[:nq] if len(vals) > 1 else vals[0][:nq]
        i_np = np.concatenate(ids, axis=0)[:nq] if len(ids) > 1 else ids[0][:nq]
        return jnp.asarray(v_np), jnp.asarray(i_np)

    # -- sharding --------------------------------------------------------------
    def shard(self, n_shards: int) -> "list[IVFIndex]":
        """Split into ``n_shards`` contiguous-range views with **replicated
        centroids** — the sparse-sharding seam.

        Every view keeps the *global* k-means centroids, so each shard
        probes exactly the clusters the unsharded index probes (the probe
        top-k sees bit-identical centroid similarities); its inverted lists
        hold only the members in its row range, re-based to local ids. The
        per-shard candidate set is the unsharded candidate set intersected
        with the shard, so merging per-shard top-k lists reconstructs the
        unsharded result exactly (canonical in-row order + lowest-shard-
        wins merge ties = canonical global order).
        """
        from repro.retrieval.sharded import shard_bounds

        buckets_np = np.asarray(self.buckets)
        mask_np = np.asarray(self.bucket_mask)
        c = self.n_clusters
        views: list[IVFIndex] = []
        for start, stop in shard_bounds(int(self.embeddings.shape[0]), n_shards):
            rows = [
                buckets_np[ci][mask_np[ci]] for ci in range(c)
            ]
            rows = [r[(r >= start) & (r < stop)] - start for r in rows]
            cap_s = max(max((r.size for r in rows), default=0), 1)
            b = np.full((c, cap_s), -1, np.int32)
            for ci, r in enumerate(rows):
                b[ci, : r.size] = r.astype(np.int32)
            bj = jnp.asarray(b)
            views.append(
                IVFIndex(self.centroids, bj, bj >= 0, self.embeddings[start:stop])
            )
        return views

    def recall_vs_exact(self, queries: jnp.ndarray, k: int, *, n_probe: int = 4) -> float:
        """Measured recall@k against exact MIPS — calibration telemetry.

        The exact :class:`DenseIndex` oracle is built lazily **once** and
        reused across calls (calibration runs this per serve epoch; the
        rebuilt-every-call version re-normalized and re-placed the whole
        corpus each time)."""
        exact = getattr(self, "_exact_cache", None)
        if exact is None:
            from repro.retrieval.index import DenseIndex

            exact = self._exact_cache = DenseIndex(
                self.embeddings, assume_normalized=True
            )
        ev, ei = exact.search_batch(queries, k)
        _, ai = self.search_batch(queries, k, n_probe=n_probe)
        ei_np, ai_np = np.asarray(ei), np.asarray(ai)
        hits = sum(
            len(set(ei_np[i].tolist()) & set(ai_np[i].tolist())) for i in range(ei_np.shape[0])
        )
        return hits / float(ei_np.size)
