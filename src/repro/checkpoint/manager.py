"""Incremental LSM graph checkpoints.

Design (mirrors the paper's crash-integrity argument, §7.3): every artifact
is an immutable flat file; a checkpoint is a manifest pointing at files; the
manifest is written LAST via atomic rename, so a crash mid-save can never
corrupt a restorable state — at worst the newest checkpoint is absent and
the previous one still points at complete files. Partitions are immutable,
so only partitions not already in the checkpoint are written
(content-addressed by a hash of their edges).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Any, Dict

import numpy as np

from ..core.failpoints import failpoint
from ..core.integrity import fsync_dir

__all__ = ["save_lsm", "restore_lsm"]


def _partition_digest(part) -> str:
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(part.src).tobytes())
    h.update(np.ascontiguousarray(part.dst).tobytes())
    return h.hexdigest()[:16]


def save_lsm(tree, directory: str) -> Dict[str, Any]:
    """Write LSM partitions not already present; returns the graph manifest.

    Partitions that already live in a content-addressed `PartitionStore`
    (a `GraphDB`'s disk tier) are HARD-LINKED into the checkpoint directory
    instead of re-serialized — the checkpoint is then a set of refs into
    the same immutable files, costing no data copy and surviving store GC
    (the inode lives until the last link drops). RAM partitions fall back
    to the npz path. Accepts a GraphDB or a bare LSMTree.

    Live buffers are captured too (`buffers.npz`, columns included) — the
    old checkpoints silently dropped unflushed edges, so a restore lost
    everything after the last flush. With buffers in the manifest the
    checkpoint is a complete recovery root on its own; the store's WAL
    segments are never referenced (restore needs no WAL replay)."""
    from ..core.disk import DiskPartition

    if hasattr(tree, "tree"):  # a GraphDB quacks like its tree
        tree = tree.tree
    os.makedirs(directory, exist_ok=True)
    manifest = {"levels": [], "intervals": {
        "n_partitions": tree.intervals.n_partitions,
        "interval_len": tree.intervals.interval_len,
    }, "written": 0, "reused": 0, "linked": 0,
        "column_dtypes": {k: np.dtype(dt).str
                          for k, dt in tree.column_dtypes.items()}}
    for li, level in enumerate(tree.levels):
        lvl = []
        for pi, part in enumerate(level):
            if isinstance(part, DiskPartition) and not part.dirty:
                fname = os.path.basename(part.path)
                fpath = os.path.join(directory, fname)
                if not os.path.exists(fpath):
                    failpoint("store.link")
                    try:
                        os.link(part.path, fpath)
                    except OSError:
                        shutil.copy2(part.path, fpath)
                    manifest["linked"] += 1
                else:
                    manifest["reused"] += 1
                entry = {"file": fname, "interval": list(part.interval),
                         "n_edges": part.n_edges, "format": "pal"}
                if part.dead is not None and part.dead.any():
                    dname = fname[:-4] + ".dead.npy"
                    with open(os.path.join(directory, dname), "wb") as df:
                        np.save(df, np.asarray(part.dead))
                    entry["dead_file"] = dname
                lvl.append(entry)
                continue
            digest = _partition_digest(part)
            fname = f"part_{digest}.npz"
            fpath = os.path.join(directory, fname)
            if not os.path.exists(fpath):
                cols = {f"col_{k}": np.asarray(v)
                        for k, v in part.columns.items()}
                np.savez(fpath, src=np.asarray(part.src),
                         dst=np.asarray(part.dst),
                         etype=np.asarray(part.etype),
                         dead=(part.dead if part.dead is not None
                               else np.zeros(0, bool)), **cols)
                manifest["written"] += 1
            else:
                manifest["reused"] += 1
            lvl.append({"file": fname, "interval": list(part.interval),
                        "n_edges": part.n_edges, "format": "npz"})
        manifest["levels"].append(lvl)
    # live (unflushed) buffers — staged internal-ID arrays, columns included
    if any(len(b) for b in getattr(tree, "buffers", [])):
        arrays = {}
        for j, b in enumerate(tree.buffers):
            if len(b) == 0:
                continue
            st = b.staging()
            arrays[f"b{j}_src"] = np.array(st.src)
            arrays[f"b{j}_dst"] = np.array(st.dst)
            arrays[f"b{j}_etype"] = np.array(st.etype)
            for k, v in st.columns.items():
                arrays[f"b{j}_col_{k}"] = np.array(v)
        tmp = os.path.join(directory, "buffers.npz.tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, os.path.join(directory, "buffers.npz"))
        manifest["buffers"] = "buffers.npz"
    tmp = os.path.join(directory, "GRAPH_MANIFEST.json.tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(directory, "GRAPH_MANIFEST.json"))
    fsync_dir(directory)
    return manifest


def restore_lsm(directory: str, column_dtypes=None, **lsm_kwargs):
    """Rebuild an LSMTree from a graph manifest (npz or linked .pal files),
    live buffers included — restore resumes with the exact unflushed edge
    set (and attribute values) the checkpoint captured."""
    from ..core.disk import open_partition_file
    from ..core.lsm import LSMTree
    from ..core.pal import IntervalMap, build_partition

    with open(os.path.join(directory, "GRAPH_MANIFEST.json")) as f:
        manifest = json.load(f)
    iv = IntervalMap(n_partitions=manifest["intervals"]["n_partitions"],
                     interval_len=manifest["intervals"]["interval_len"])
    n_levels = len(manifest["levels"])
    branching = 1
    if n_levels > 1:
        branching = len(manifest["levels"][1]) // len(manifest["levels"][0])
    if column_dtypes is None:
        column_dtypes = {k: np.dtype(s)
                         for k, s in manifest.get("column_dtypes", {}).items()}
    tree = LSMTree(iv, n_levels=n_levels, branching=max(branching, 1),
                   column_dtypes=column_dtypes or {}, **lsm_kwargs)
    for li, lvl in enumerate(manifest["levels"]):
        for pi, entry in enumerate(lvl):
            fpath = os.path.join(directory, entry["file"])
            if entry.get("format", "npz") == "pal":
                part = open_partition_file(fpath)
                if entry.get("dead_file"):
                    part.dead = np.load(
                        os.path.join(directory, entry["dead_file"]))
                tree.levels[li][pi] = part
                continue
            data = np.load(fpath)
            cols = {k[4:]: data[k] for k in data.files if k.startswith("col_")}
            part = build_partition(tuple(entry["interval"]), data["src"],
                                   data["dst"], data["etype"], cols,
                                   presorted=True)
            if data["dead"].size:
                part.dead = data["dead"]
            tree.levels[li][pi] = part
    if manifest.get("buffers"):
        data = np.load(os.path.join(directory, manifest["buffers"]))
        for j in range(len(tree.buffers)):
            if f"b{j}_src" not in data.files:
                continue
            cols = {k[len(f"b{j}_col_"):]: data[k] for k in data.files
                    if k.startswith(f"b{j}_col_")}
            # buffer arrays are staged INTERNAL ids: restore them directly
            # (insert_edges would re-hash and re-route)
            tree.buffers[j].extend(data[f"b{j}_src"], data[f"b{j}_dst"],
                                   data[f"b{j}_etype"], cols)
            tree._buffered += int(data[f"b{j}_src"].shape[0])
    return tree
