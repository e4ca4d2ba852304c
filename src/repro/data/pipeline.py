"""Federated round batching: client selection, data limiting, packing.

A federated round batch is a fixed-shape pytree:
    features : (K, S, B, T, F)   S = local steps, B = local batch
    labels   : (K, S, B, U)
    label_len, frame_len : (K, S, B)
    mask     : (K, S, B)  1.0 for real examples, 0.0 for padding
    n_k      : (K,)       number of real examples per client (paper's n_k)

The *data limit* L (paper §4.2.1) caps how many examples a client
contributes in one round — the paper's dial between non-IID (L=None)
and near-IID (L=1). The full per-speaker dataset is still traversed
over multiple rounds via per-client cursors.

Packing is pure numpy fancy-indexing against the corpus arena
(one gather per field, no per-example Python loop); the original
per-example loop survives behind ``legacy=True`` solely as the parity
oracle for tests/benchmarks and will be removed once a few PRs of CI
history have exercised the vectorized path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.data.strategies import get_strategy
from repro.data.synthetic import label_shuffle
from repro.profile.trace import span


@dataclasses.dataclass
class RoundBatch:
    features: np.ndarray
    labels: np.ndarray
    label_len: np.ndarray
    frame_len: np.ndarray
    mask: np.ndarray
    n_k: np.ndarray

    def tree(self):
        return dataclasses.asdict(self)

    def engine_batch(self) -> dict:
        """The round engine's input layout (mask enters as "weight")."""
        with span("sampler.engine_batch"):
            return {"features": self.features, "labels": self.labels,
                    "frame_len": self.frame_len, "label_len": self.label_len,
                    "weight": self.mask}

    def pad_steps(self, steps: int) -> "RoundBatch":
        """Append weight-0 local steps up to ``steps`` (exact no-ops
        under the engine's n_k weighting) so differently-shaped rounds
        can share one compiled round fn."""
        S = self.mask.shape[1]
        if steps <= S:
            return self

        def pad(a):
            extra = np.zeros((a.shape[0], steps - S) + a.shape[2:], a.dtype)
            return np.concatenate([a, extra], axis=1)

        return RoundBatch(pad(self.features), pad(self.labels),
                          pad(self.label_len), pad(self.frame_len),
                          pad(self.mask), self.n_k)


class FederatedSampler:
    """Selects K clients per round and packs their (possibly limited)
    local datasets into fixed-shape round batches."""

    def __init__(
        self,
        corpus,
        clients_per_round: int,
        local_batch_size: int,
        data_limit: Optional[int] = None,
        local_epochs: int = 1,
        seed: int = 0,
        max_steps=None,
        steps: Optional[int] = None,
        strategy: str = "uniform",
        legacy: bool = False,
        label_shuffle_rate: float = 0.0,
    ):
        self.corpus = corpus
        self.K = clients_per_round
        self.b = local_batch_size
        self.data_limit = data_limit
        self.local_epochs = local_epochs
        self.rng = np.random.default_rng(seed)
        self.legacy = legacy
        self._select = get_strategy(strategy)
        # Data-plane adversary (repro.core.corruption "label_shuffle"):
        # each round, Bernoulli(rate)-selected clients get their round
        # labels permuted among their real examples. A dedicated RNG
        # keeps the selection/packing stream byte-identical to an
        # uncorrupted run at rate 0.
        self.label_shuffle_rate = float(label_shuffle_rate)
        self._corrupt_rng = np.random.default_rng((seed + 1) * 0xC0FFEE)
        self.corrupted_counts: list = []
        # Per-client cursors so data-limited rounds still traverse all
        # data. LAZY dicts keyed by client id: under a VirtualPopulation
        # N >> K and only visited clients may cost memory (each order is
        # seeded by its own id, so lazy creation is bit-identical to the
        # historical eager list for plain corpora).
        self._seed = seed
        self._cursors: dict = {}
        self._orders: dict = {}
        self._base_counts, self._base_of = self._corpus_counts(corpus)
        if legacy and self._base_of is not None:
            raise ValueError(
                "the legacy per-example packer is the plain-corpus parity "
                "oracle; virtual populations use the vectorized path"
            )
        # Fixed max local steps for jit-stable shapes. ``steps`` forces
        # an exact S (sweep runners pad every point to one shape so a
        # single compiled round fn serves the whole grid).
        self.steps = (int(steps) if steps is not None else
                      self.natural_steps(corpus, local_batch_size,
                                         data_limit=data_limit,
                                         local_epochs=local_epochs,
                                         max_steps=max_steps))

    @staticmethod
    def _corpus_counts(corpus):
        """(counts histogram, virtual->base map or None). The histogram
        is indexed by BASE speaker row; plain corpora are their own
        base (identity, ``base_of`` None)."""
        base_of = getattr(corpus, "base_of", None)
        counts = getattr(corpus, "base_counts", None)
        if counts is None:
            counts = getattr(corpus, "counts", None)
        if counts is None:
            counts = np.array([s["n"] for s in corpus.speakers], np.int64)
        return np.asarray(counts, np.int64), base_of

    @staticmethod
    def natural_steps(corpus, local_batch_size: int,
                      data_limit: Optional[int] = None, local_epochs: int = 1,
                      max_steps: Optional[int] = None) -> int:
        """The local-step count a round needs to hold every selected
        client's (possibly limited) contribution — the single source of
        truth for batch shapes AND for CFMQ mu accounting (sweeps)."""
        if data_limit is not None:
            n_max = data_limit
        else:
            counts, _ = FederatedSampler._corpus_counts(corpus)
            n_max = int(counts.max())
        steps = max(1, int(np.ceil(local_epochs * n_max / local_batch_size)))
        if max_steps is not None:
            steps = min(steps, max_steps)
        return steps

    def _count(self, cid: int) -> int:
        """Example count of one client (virtual ids map to their base
        speaker's histogram slot — a clone holds the same data)."""
        base = cid % len(self._base_counts) if self._base_of is not None else cid
        return int(self._base_counts[base])

    def _order(self, cid: int) -> np.ndarray:
        """The client's live shuffle order, created on first visit from
        its id-seeded generator (clones of one speaker get independent
        orders; plain corpora get the historical eager order bitwise)."""
        o = self._orders.get(cid)
        if o is None:
            o = np.random.default_rng(self._seed + 7 * cid).permutation(self._count(cid))
            self._orders[cid] = o
        return o

    def _client_indices(self, cid: int) -> np.ndarray:
        """This round's example indices for one client (length = limit),
        advancing the cursor with a reshuffle at each full pass. Loops
        over *passes* (segments), never over examples."""
        n = self._count(cid)
        limit = min(self.data_limit, n) if self.data_limit is not None else n
        c = int(self._cursors.get(cid, 0))
        order = self._order(cid)
        pos = c % n
        if limit <= n - pos and not (pos == 0 and c > 0):
            # fast path: the whole contribution sits inside the current
            # pass — return a view of the live order, no copies
            self._cursors[cid] = c + limit
            return order[pos:pos + limit]
        out = np.empty(limit, np.int64)
        filled = 0
        while filled < limit:
            if c % n == 0 and c > 0:
                order = self.rng.permutation(n)
                self._orders[cid] = order
            take = min(n - c % n, limit - filled)
            out[filled:filled + take] = order[c % n:c % n + take]
            filled += take
            c += take
        self._cursors[cid] = c
        return out

    def _gather_indices(self, chosen: np.ndarray):
        """(K, S*b) example-index matrix (-1 = padding) + per-client n_k.

        The K-iteration loop only advances cursors/reshuffles (which is
        inherently sequential in the RNG stream); all example payloads
        move in the single arena gather in ``next_round``."""
        E = self.steps * self.b
        ex = np.full((len(chosen), E), -1, np.int64)
        n_k = np.zeros((len(chosen),), np.float32)
        for j, cid in enumerate(chosen):
            idx = self._client_indices(int(cid))
            if self.local_epochs > 1:
                idx = np.tile(idx, self.local_epochs)
            m = min(len(idx), E)
            ex[j, :m] = idx[:m]
            n_k[j] = m
        return ex, n_k

    def _shuffle_labels(self, rb: RoundBatch) -> RoundBatch:
        """Apply the label_shuffle adversary to Bernoulli-selected
        clients, in place on the freshly-packed (copied) arrays; the
        realized corrupted-client count is appended per round so
        drivers can report it next to the in-graph corruption metric."""
        K = rb.labels.shape[0]
        hit = self._corrupt_rng.random(K) < self.label_shuffle_rate
        # (K, S, b, ...) -> flat (K, S*b, ...) views onto the same memory
        labels = rb.labels.reshape(K, -1, rb.labels.shape[-1])
        label_len = rb.label_len.reshape(K, -1)
        mask = rb.mask.reshape(K, -1)
        for k in np.flatnonzero(hit):
            label_shuffle(labels[k], label_len[k], mask[k] > 0,
                          self._corrupt_rng)
        self.corrupted_counts.append(int(hit.sum()))
        return rb

    def next_round(self) -> RoundBatch:
        with span("sampler.next_round"):
            rb = self._next_round()
            if self.label_shuffle_rate > 0.0:
                rb = self._shuffle_labels(rb)
            return rb

    def _next_round(self) -> RoundBatch:
        K, b, S = self.K, self.b, self.steps
        chosen = np.asarray(self._select(self.rng, self.corpus, K), np.int64)
        if self.legacy:
            return self._next_round_legacy(chosen)
        ex, n_k = self._gather_indices(chosen)
        pad = ex < 0
        np.copyto(ex, 0, where=pad)                  # safe gather index
        # (K, 1) arena rows: virtual client ids gather their base
        # speaker's row — the only O(K) touch of the population
        base = self._base_of(chosen) if self._base_of is not None else chosen
        rows = np.asarray(base, np.int64)[:, None]
        c = self.corpus
        # fancy-indexing copies, so padded slots can be zeroed in place
        feats = c.arena_features[rows, ex]           # (K, S*b, T, F)
        labels = c.arena_labels[rows, ex]
        label_len = c.arena_label_len[rows, ex]
        frame_len = c.arena_frame_len[rows, ex]
        if pad.any():
            feats[pad] = 0.0
            labels[pad] = 0
            label_len[pad] = 0
            frame_len[pad] = 0
        mask = (~pad).astype(np.float32)
        T, F = feats.shape[2:]
        U = labels.shape[-1]
        return RoundBatch(
            feats.reshape(K, S, b, T, F),
            labels.reshape(K, S, b, U),
            label_len.reshape(K, S, b),
            frame_len.reshape(K, S, b),
            mask.reshape(K, S, b),
            n_k,
        )

    # ------------------------------------------------------------------
    # Legacy per-example packing: parity oracle only (see module doc).
    # ------------------------------------------------------------------

    def _client_examples(self, cid: int):
        n = self._count(cid)
        order = self._order(cid)
        limit = min(self.data_limit, n) if self.data_limit is not None else n
        idx = []
        for _ in range(limit):
            c = self._cursors.get(cid, 0)
            if c % n == 0 and c > 0:
                # reshuffle each full pass
                self._orders[cid] = self.rng.permutation(n)
                order = self._orders[cid]
            idx.append(order[c % n])
            self._cursors[cid] = c + 1
        return np.asarray(idx, np.int64)

    def _next_round_legacy(self, chosen) -> RoundBatch:
        K, b, S = self.K, self.b, self.steps
        c0 = self.corpus.speakers[0]
        T, F = c0["features"].shape[1:]
        U = c0["labels"].shape[1]
        feats = np.zeros((K, S, b, T, F), np.float32)
        labels = np.zeros((K, S, b, U), np.int32)
        label_len = np.zeros((K, S, b), np.int32)
        frame_len = np.zeros((K, S, b), np.int32)
        mask = np.zeros((K, S, b), np.float32)
        n_k = np.zeros((K,), np.float32)
        for j, cid in enumerate(chosen):
            idx = self._client_examples(int(cid))
            idx = np.tile(idx, self.local_epochs)[: S * b]
            n_k[j] = len(idx)
            sp = self.corpus.speakers[int(cid)]
            for e, ei in enumerate(idx):
                s, bi = divmod(e, b)
                feats[j, s, bi] = sp["features"][ei]
                labels[j, s, bi] = sp["labels"][ei]
                label_len[j, s, bi] = sp["label_len"][ei]
                frame_len[j, s, bi] = sp["frame_len"][ei]
                mask[j, s, bi] = 1.0
        return RoundBatch(feats, labels, label_len, frame_len, mask, n_k)


def per_client_eval_batch(corpus, client_ids, n: int = 4) -> dict:
    """A stacked per-client eval batch for the per-client evaluation
    plane (``repro.core.clienteval``): each tracked client's first
    ``n`` arena examples, in the engine-batch layout with a leading
    client axis —

        features : (C, n, T, F)    labels : (C, n, U)
        frame_len, label_len, weight : (C, n)

    The FIRST examples, not a draw: the panel must measure the same
    utterances every round so per-client curves move only because the
    model moved. Clients with fewer than ``n`` examples pad with
    weight-0 slots (clipped gather, then zeroed). Virtual client ids
    gather their base speaker's arena row."""
    ids = np.asarray(client_ids, np.int64)
    base_of = getattr(corpus, "base_of", None)
    base = np.asarray(base_of(ids) if base_of is not None else ids, np.int64)
    counts = np.asarray(
        getattr(corpus, "base_counts", None)
        if getattr(corpus, "base_counts", None) is not None
        else corpus.counts,
        np.int64,
    )[base]
    cols = np.arange(n, dtype=np.int64)[None, :]
    pad = cols >= counts[:, None]
    ex = np.minimum(cols, np.maximum(counts[:, None] - 1, 0))
    rows = base[:, None]
    feats = corpus.arena_features[rows, ex]
    labels = corpus.arena_labels[rows, ex]
    label_len = corpus.arena_label_len[rows, ex]
    frame_len = corpus.arena_frame_len[rows, ex]
    if pad.any():
        feats[pad] = 0.0
        labels[pad] = 0
        label_len[pad] = 0
        frame_len[pad] = 0
    return {
        "features": feats,
        "labels": labels,
        "frame_len": frame_len,
        "label_len": label_len,
        "weight": (~pad).astype(np.float32),
    }


def pack_round(examples: dict, K: int, steps: int, batch: int) -> RoundBatch:
    """Pack a flat example dict into a (K, steps, batch, ...) round —
    used for IID baselines where examples are drawn from the global pool."""
    need = K * steps * batch
    n = examples["labels"].shape[0]
    idx = np.resize(np.arange(n), need)
    feats = examples["features"][idx].reshape(K, steps, batch, *examples["features"].shape[1:])
    labels = examples["labels"][idx].reshape(K, steps, batch, -1)
    label_len = examples["label_len"][idx].reshape(K, steps, batch)
    frame_len = examples["frame_len"][idx].reshape(K, steps, batch)
    mask = np.ones((K, steps, batch), np.float32)
    n_k = np.full((K,), steps * batch, np.float32)
    return RoundBatch(feats, labels, label_len, frame_len, mask, n_k)
