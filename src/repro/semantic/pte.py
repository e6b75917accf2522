"""Pre-trained Text Encoder (PTE) substrate — §4.4.

The container is offline, so Qwen3-Embedding / BGE are stood in by a small
deterministic transformer encoder over synthetic "descriptions" (token
sequences derived from an entity's id and graph neighborhood). The system
treats H_sem as an opaque [E, d_l] buffer either way, so every systems claim
(decoupled offline encode, unload, GPU-resident gather) is exercised for real;
only the linguistic content is synthetic.

To make the semantic prior *useful* (the paper's +MRR effect), descriptions
mention neighbor entities, so entities that co-occur in the graph get nearby
embeddings — the same reason real textual priors help on sparse KGs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.kg import KnowledgeGraph

_DESC_LEN = 16
_VOCAB = 4096


@dataclasses.dataclass
class PTEConfig:
    name: str = "stub-qwen3-embedding-0.6b"
    d_l: int = 1024        # Qwen3-Embedding-0.6B output dim
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 4
    seed: int = 1234


class StubPTE:
    """Frozen stub encoder with a real (small) transformer forward pass, so
    encoding pays a genuine per-batch inference cost."""

    def __init__(self, cfg: PTEConfig = PTEConfig()):
        self.cfg = cfg
        key = jax.random.PRNGKey(cfg.seed)
        d, h = cfg.d_model, cfg.d_model * 4
        ks = jax.random.split(key, 4 + 4 * cfg.n_layers)
        s = 1.0 / np.sqrt(d)
        self.params = {
            "tok": jax.random.normal(ks[0], (_VOCAB, d)) * s,
            "pos": jax.random.normal(ks[1], (_DESC_LEN, d)) * s,
            "out_w": jax.random.normal(ks[2], (d, cfg.d_l)) * s,
            "out_b": jnp.zeros((cfg.d_l,)),
        }
        for i in range(cfg.n_layers):
            k0, k1, k2, k3 = ks[4 + 4 * i : 8 + 4 * i]
            self.params[f"l{i}_qkv"] = jax.random.normal(k0, (d, 3 * d)) * s
            self.params[f"l{i}_o"] = jax.random.normal(k1, (d, d)) * s
            self.params[f"l{i}_up"] = jax.random.normal(k2, (d, h)) * s
            self.params[f"l{i}_down"] = jax.random.normal(k3, (h, d)) * s
        self.unloaded = False

    # -- synthetic descriptions ------------------------------------------------
    @staticmethod
    def descriptions(kg: KnowledgeGraph, ent_ids: np.ndarray) -> np.ndarray:
        """Token sequence per entity: hashed id tokens + first neighbors.

        Fully vectorized (one numpy pass per neighbor position, not a Python
        loop per entity) so store precompute on large synthetic KGs is not
        host-bound on tokenization."""
        indptr, rels, tails = kg.relations_by_head
        ids = np.asarray(ent_ids, dtype=np.int64).ravel()
        toks = np.zeros((len(ids), _DESC_LEN), dtype=np.int32)
        toks[:, 0] = ids % _VOCAB
        # (e * K) % V == ((e % V) * (K % V)) % V — overflow-safe in int64.
        toks[:, 1] = (ids % _VOCAB) * (2654435761 % _VOCAB) % _VOCAB
        lo = indptr[ids]
        max_pairs = (_DESC_LEN - 2) // 2
        deg = np.minimum(indptr[ids + 1] - lo, max_pairs)
        for j in range(max_pairs):
            m = deg > j
            if not m.any():
                break
            src = lo[m] + j
            toks[m, 2 + 2 * j] = rels[src] % _VOCAB
            toks[m, 3 + 2 * j] = tails[src] % _VOCAB
        return toks

    # -- forward ---------------------------------------------------------------
    def encode_tokens(self, tokens: jnp.ndarray) -> jnp.ndarray:
        if self.unloaded:
            raise RuntimeError("PTE has been unloaded (decoupled phase ended)")
        p = self.params
        x = p["tok"][tokens] + p["pos"][None, :, :]
        d = self.cfg.d_model
        nh = self.cfg.n_heads
        hd = d // nh
        for i in range(self.cfg.n_layers):
            qkv = x @ p[f"l{i}_qkv"]
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(t):
                return t.reshape(t.shape[0], t.shape[1], nh, hd).transpose(0, 2, 1, 3)

            q, k, v = heads(q), heads(k), heads(v)
            att = jax.nn.softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd), axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", att, v).transpose(0, 2, 1, 3).reshape(x.shape)
            x = x + o @ p[f"l{i}_o"]
            x = x + jax.nn.gelu(x @ p[f"l{i}_up"]) @ p[f"l{i}_down"]
        pooled = x.mean(axis=1)
        return pooled @ p["out_w"] + p["out_b"]

    def encode_entities(self, kg: KnowledgeGraph, ent_ids: np.ndarray) -> jnp.ndarray:
        return self.encode_tokens(jnp.asarray(self.descriptions(kg, ent_ids)))

    def unload(self) -> None:
        """§4.4: 'once H_sem is generated, the PTE is unloaded from memory'."""
        self.params = None
        self.unloaded = True


def encode_normalized_batches(kg: KnowledgeGraph, pte: StubPTE,
                              batch_size: int = 256):
    """Yield L2-normalized encoder outputs in fixed global batch boundaries.

    Shared by the in-memory ``precompute_semantic_table`` and the streaming
    ``semantic/store.py::precompute_semantic_table_to_store``. Both consume
    the SAME batch boundaries (``range(0, E, batch_size)``) so the jitted
    encoder sees identical shapes and the two paths stay bit-identical;
    normalization is per-row, hence batch-local."""
    enc = jax.jit(pte.encode_tokens)
    ids = np.arange(kg.n_entities)
    for lo in range(0, kg.n_entities, batch_size):
        chunk = ids[lo : lo + batch_size]
        block = np.array(enc(jnp.asarray(StubPTE.descriptions(kg, chunk))))
        block /= np.linalg.norm(block, axis=1, keepdims=True) + 1e-6
        yield block


def precompute_semantic_table(
    kg: KnowledgeGraph,
    pte: Optional[StubPTE] = None,
    batch_size: int = 256,
    unload: bool = True,
    smooth: float = 0.5,
) -> np.ndarray:
    """Offline pre-computation phase (Eq. 10): encode every entity, L2
    normalize, then one hop of neighbor smoothing (stands in for the semantic
    relatedness real descriptions carry). Returns host numpy; callers register
    it as a device-resident buffer.

    This is the FULL-RESIDENT path (small graphs / ablation). At scale, use
    ``semantic/store.py::precompute_semantic_table_to_store`` — it streams the
    same computation shard-by-shard to disk without ever holding the
    ``[E, d_l]`` table in host RAM, and its fp32 output is bit-identical."""
    pte = pte or StubPTE()
    table = np.concatenate(
        list(encode_normalized_batches(kg, pte, batch_size)), axis=0)
    if smooth > 0:
        nb = np.zeros_like(table)
        cnt = np.ones((kg.n_entities, 1))
        np.add.at(nb, kg.triples[:, 0], table[kg.triples[:, 2]])
        np.add.at(cnt, kg.triples[:, 0], 1.0)
        np.add.at(nb, kg.triples[:, 2], table[kg.triples[:, 0]])
        np.add.at(cnt, kg.triples[:, 2], 1.0)
        table = table + smooth * nb / cnt
        table /= np.linalg.norm(table, axis=1, keepdims=True) + 1e-6
    if unload:
        pte.unload()
    return table.astype(np.float32)
