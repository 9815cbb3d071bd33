"""A configuration's edges, made from the seed, and the durable store
loaded with them the way users load one (`ServiceDB.insert_edges`)."""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Tuple

import numpy as np

from .gen import GENERATORS, labelling


def edges(cfg: Dict[str, Any], seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """The configuration's input edge list: its generator's (u, v) with the
    configuration's `generator` arguments."""
    gen = cfg["generator"]
    kw = {k: v for k, v in gen.items() if k != "name"}
    return GENERATORS[gen["name"]](seed=seed, **kw)


def stored_edges(cfg: Dict[str, Any], u: np.ndarray, v: np.ndarray):
    """The directed edges the store holds: an undirected graph is stored
    both ways (u->v and v->u), a directed one as given."""
    if cfg["directed"]:
        return u, v
    return np.concatenate([u, v]), np.concatenate([v, u])


def search_keys(cfg: Dict[str, Any], seed: int, src: np.ndarray,
                dst: np.ndarray, count: int, min_degree: int) -> np.ndarray:
    """`count` distinct traversal roots among vertices with at least
    `min_degree` neighbours besides themselves, in the order a window
    takes them. Where the generator fixes the structure apart from the
    labels (`structure_seed`), the roots are the same structural vertices
    for every seed, in its labelling: every seed then does the same work."""
    n = n_vertices(cfg)
    deg = np.bincount(src[src != dst], minlength=n)
    gen = cfg["generator"]
    if "structure_seed" in gen:
        labels = labelling(gen["scale"], seed)
        rng = np.random.default_rng([gen["structure_seed"], 0xBF5])
        candidates = np.flatnonzero(deg[labels] >= min_degree)
        pick = rng.choice(candidates, size=min(count, candidates.shape[0]),
                          replace=False)
        return labels[pick]
    rng = np.random.default_rng([seed, 0xBF5])
    candidates = np.flatnonzero(deg >= min_degree)
    return rng.choice(candidates, size=min(count, candidates.shape[0]),
                      replace=False)


def n_vertices(cfg: Dict[str, Any]) -> int:
    return int(cfg["vertices"])


def load_store(cfg: Dict[str, Any], directory: str, src: np.ndarray,
               dst: np.ndarray, log) -> Any:
    """A durable ServiceDB with the configuration's store settings, fed
    `src -> dst` through `insert_edges` in `load_batch` batches, then
    checkpointed so that no maintenance runs on into the window."""
    from repro.core import ServiceDB

    svc = ServiceDB.create(os.path.join(directory, "store"),
                           max_id=n_vertices(cfg) - 1, **cfg["store"])
    batch = int(cfg["load_batch"])
    t0 = time.perf_counter()
    for i in range(0, src.shape[0], batch):
        svc.insert_edges(src[i:i + batch], dst[i:i + batch])
    t1 = time.perf_counter()
    svc.checkpoint()
    log(f"load: {src.shape[0]} edges in {t1 - t0} s, checkpoint "
        f"{time.perf_counter() - t1} s")
    if svc.n_edges != src.shape[0]:
        svc.close()
        raise RuntimeError(f"store holds {svc.n_edges} edges, "
                           f"{src.shape[0]} were inserted")
    return svc
