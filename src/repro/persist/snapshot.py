"""Checksummed engine snapshots + recovery (docs/persistence.md).

A durable-index directory holds three kinds of entry:

    MANIFEST.json            atomic pointer to the last COMPLETE snapshot
    snap-NNNNNN/*.npy        per-segment CRC-verified array files
    wal-############.log     the write-ahead mutation log chain

``save_snapshot`` captures (WAL position, engine state) atomically under
the engine's own mutation lock, serializes every segment to a *new*
``snap-`` directory, and only then atomically replaces the manifest — so a
crash mid-snapshot leaves the previous manifest pointing at the previous,
still-complete snapshot, and a torn segment is never loadable (the
manifest that would have named it was never written). After the manifest
is durable, older snapshots and WAL files it fully covers are garbage
collected (this is the WAL truncation story).

Recovery (``open_engine``) = load the manifest's snapshot (every segment
CRC-checked), replay WAL records past the snapshot's ``wal_seq`` through
the engine's own deterministic mutators, and attach a fresh WAL writer at
the next sequence number. The result is asserted bit-identical to the
never-crashed engine across every query path (tests/test_persist.py).

Sharded engines persist one sub-manifest per shard (``shard-NN/
manifest.json``, itself CRC'd by the top manifest) so each shard's
segment set is independently verifiable.
"""
from __future__ import annotations

import io as _io
import json
import os
import shutil
import threading
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import ivf as ivf_mod
from repro.core import lists as lists_mod
from repro.core.lists import pack_filter_mask
from repro.core.pq import PQCodebook
from repro.engine.engine import EngineConfig, SearchEngine
from repro.engine.sharded import ShardedEngine, _ShardState
from repro.kernels import ops as ops_mod
from repro.persist import io as pio
from repro.persist import wal as wal_mod
from repro.persist.errors import CorruptSnapshotError, NoSnapshotError

MANIFEST_NAME = "MANIFEST.json"
SCHEMA = 2
_KNOWN_SCHEMAS = (1, 2)  # 1 = pre-replication (no term/parent/delta fields)
_SNAPSHOT_KINDS = ("single", "sharded")


class RecoveryInfo(NamedTuple):
    """What ``open_engine`` reconstructed, for assertions and ops logs."""

    snapshot: str        # snap-NNNNNN directory the manifest named
    wal_seq: int         # mutations already folded into that snapshot
    replayed: int        # WAL records replayed on top of it
    last_seq: int        # wal_seq + replayed == total acknowledged mutations
    truncated_bytes: int # torn tail dropped from the final WAL file (crash
    #                      mid-append; 0 on a clean shutdown)
    term: int = 0        # fencing term the manifest recorded (replication)


# ---------------------------------------------------------------------------
# segment primitives
# ---------------------------------------------------------------------------

def _npy_bytes(arr: np.ndarray) -> bytes:
    bio = _io.BytesIO()
    np.save(bio, np.asarray(arr), allow_pickle=False)
    return bio.getvalue()


class _DeltaStats:
    """Per-checkpoint byte accounting: what was rewritten vs referenced."""

    def __init__(self):
        self.bytes_written = 0
        self.bytes_reused = 0
        self.segments_written = 0
        self.segments_reused = 0

    def as_meta(self) -> dict:
        return {"bytes_written": self.bytes_written,
                "bytes_reused": self.bytes_reused,
                "segments_written": self.segments_written,
                "segments_reused": self.segments_reused}


def _write_segments(directory: str, seg_dir: str,
                    arrays: dict[str, np.ndarray],
                    parent: dict | None = None,
                    stats: _DeltaStats | None = None) -> dict:
    """Write each array as ``<seg_dir>/<name>.npy``; return manifest entries
    (file paths relative to the root ``directory``).

    **Delta snapshots**: when ``parent`` holds the previous manifest's
    entries for the same segment set, any array whose serialized bytes
    CRC+size-match the parent entry is NOT rewritten — the new manifest
    references the parent's file in place (``_gc`` keeps every referenced
    snapshot directory alive). A delete-only interval thus rewrites only
    ids/sizes/live_bits, never the code or base payloads.
    """
    entries = {}
    for name, arr in arrays.items():
        data = _npy_bytes(arr)
        crc = pio.crc32(data)
        old = None if parent is None else parent.get(name)
        if (old is not None and old.get("crc") == crc
                and old.get("size") == len(data)
                and os.path.exists(os.path.join(directory, old["file"]))):
            entries[name] = {"file": old["file"], "crc": crc,
                             "size": len(data)}
            if stats is not None:
                stats.bytes_reused += len(data)
                stats.segments_reused += 1
            continue
        rel = os.path.join(os.path.relpath(seg_dir, directory),
                           f"{name}.npy")
        pio.write_bytes(os.path.join(directory, rel), data)
        entries[name] = {"file": rel, "crc": crc, "size": len(data)}
        if stats is not None:
            stats.bytes_written += len(data)
            stats.segments_written += 1
    return entries


def _read_verified(directory: str, entry: dict, what: str) -> bytes:
    path = os.path.join(directory, entry["file"])
    try:
        data = pio.read_bytes(path)
    except OSError as e:
        raise CorruptSnapshotError(
            f"{what} segment {entry['file']} unreadable: {e}") from e
    if len(data) != entry["size"]:
        raise CorruptSnapshotError(
            f"{what} segment {entry['file']} truncated: "
            f"{len(data)} bytes, manifest says {entry['size']}")
    if pio.crc32(data) != entry["crc"]:
        raise CorruptSnapshotError(
            f"{what} segment {entry['file']} failed its CRC check")
    return data


def _load_array(directory: str, entry: dict, what: str) -> np.ndarray:
    data = _read_verified(directory, entry, what)
    try:
        return np.load(_io.BytesIO(data), allow_pickle=False)
    except Exception as e:
        raise CorruptSnapshotError(
            f"{what} segment {entry['file']} undecodable: {e}") from e


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

def _manifest_crc(manifest: dict) -> int:
    """CRC of the manifest body over a canonical serialization, so the
    manifest protects its own fields (``wal_seq`` above all — a flipped
    digit there would replay the wrong WAL suffix undetected)."""
    body = {k: v for k, v in manifest.items() if k != "manifest_crc"}
    return pio.crc32(json.dumps(body, sort_keys=True,
                                separators=(",", ":")).encode("utf-8"))

def read_manifest(directory: str) -> dict:
    """The directory's manifest, or ``NoSnapshotError`` if none exists.

    A present-but-unparseable manifest is ``CorruptSnapshotError`` — the
    distinction lets boot logic initialize a fresh directory while never
    silently reinitializing a damaged one."""
    path = os.path.join(directory, MANIFEST_NAME)
    try:
        data = pio.read_bytes(path)
    except FileNotFoundError:
        raise NoSnapshotError(
            f"no {MANIFEST_NAME} in {directory} — nothing was ever "
            "checkpointed here") from None
    try:
        manifest = json.loads(data.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise CorruptSnapshotError(
            f"{path} is not valid manifest JSON: {e}") from e
    if (manifest.get("schema") not in _KNOWN_SCHEMAS
            or manifest.get("kind") not in _SNAPSHOT_KINDS):
        raise CorruptSnapshotError(
            f"{path}: unknown schema/kind "
            f"{manifest.get('schema')!r}/{manifest.get('kind')!r}")
    if manifest.get("manifest_crc") != _manifest_crc(manifest):
        raise CorruptSnapshotError(f"{path} failed its self-CRC check")
    # graceful migration: schema-1 manifests predate replication — they are
    # full (non-delta) snapshots written by term 0 with no parent chain
    manifest.setdefault("term", 0)
    manifest.setdefault("parent", None)
    return manifest


def _next_snap_name(directory: str) -> str:
    nums = [0]
    for name in os.listdir(directory):
        if name.startswith("snap-") and name[5:].isdigit():
            nums.append(int(name[5:]))
    return f"snap-{max(nums) + 1:06d}"


# ---------------------------------------------------------------------------
# save
# ---------------------------------------------------------------------------

def _config_meta(config: EngineConfig) -> dict:
    return dict(config._asdict())


def _serialize_single(engine: SearchEngine, st, directory: str,
                      snap_dir: str, parent: dict | None,
                      stats: _DeltaStats) -> tuple[dict, dict, None]:
    if engine.coarse_kind not in ("flat", "hnsw", "tree"):
        raise ValueError(
            f"cannot snapshot an engine with a custom coarse quantizer "
            f"({engine.coarse_kind!r}) — only flat/hnsw/tree rebuild "
            "deterministically from the centroids")
    arrays = dict(lists_mod.store_arrays(st.index.lists))
    arrays["centroids"] = np.asarray(st.index.centroids)
    arrays["codebook"] = np.asarray(st.index.codebook.codewords)
    if st.base is not None:
        arrays["base"] = np.asarray(st.base)
    if st.live_bits is not None:
        arrays["live_bits"] = np.asarray(st.live_bits)
    if engine.ns_member is not None:
        arrays["ns_member"] = np.asarray(engine.ns_member)
    meta = {"config": _config_meta(engine.config),
            "coarse_kind": engine.coarse_kind,
            "hnsw_m": engine.hnsw_m,
            "ef_construction": engine.ef_construction,
            "epoch": int(st.epoch),
            "n_tombstones": int(st.n_tombstones)}
    parent_segs = None if parent is None else parent.get("segments")
    return (_write_segments(directory, snap_dir, arrays, parent_segs, stats),
            meta, None)


def _parent_shard_segments(directory: str, parent: dict | None,
                           num_shards: int) -> list[dict | None]:
    """Per-shard segment tables of the parent manifest (for delta reuse);
    a shard whose sub-manifest cannot be verified simply gets no reuse."""
    out: list[dict | None] = [None] * num_shards
    if parent is None or len(parent.get("shards", ())) != num_shards:
        return out
    for j, entry in enumerate(parent["shards"]):
        try:
            sub = json.loads(_read_verified(
                directory, {"file": entry["manifest"], "crc": entry["crc"],
                            "size": entry["size"]},
                "parent shard manifest").decode("utf-8"))
            out[j] = sub["segments"]
        except (CorruptSnapshotError, KeyError, UnicodeDecodeError,
                json.JSONDecodeError):
            out[j] = None
    return out


def _serialize_sharded(engine: ShardedEngine, st: _ShardState,
                       directory: str, snap_dir: str, parent: dict | None,
                       stats: _DeltaStats) -> tuple[dict, dict, list]:
    arrays = {"centroids": np.asarray(engine.centroids),
              "codebook": np.asarray(engine.codebook.codewords)}
    if engine.member_s is not None:
        arrays["member_s"] = np.asarray(engine.member_s)
    parent_segs = None if parent is None else parent.get("segments")
    segments = _write_segments(directory, snap_dir, arrays, parent_segs,
                               stats)
    store = lists_mod.store_arrays(st.lists_s)  # 3-D, leading shard dim
    parent_sh = _parent_shard_segments(directory, parent, engine.num_shards)
    shards = []
    for j in range(engine.num_shards):
        shard_dir = os.path.join(snap_dir, f"shard-{j:02d}")
        os.makedirs(shard_dir, exist_ok=True)
        sh = {k: v[j] for k, v in store.items()}
        sh["centroids"] = np.asarray(st.centroids_s[j])
        sh["real"] = np.asarray(st.real_s[j])
        sh["gids"] = np.asarray(st.gids_s[j])
        if st.base_s is not None:
            sh["base"] = np.asarray(st.base_s[j])
        entries = _write_segments(directory, shard_dir, sh, parent_sh[j],
                                  stats)
        sub = json.dumps({"shard": j, "segments": entries},
                         indent=1).encode("utf-8")
        rel = os.path.join(os.path.relpath(shard_dir, directory),
                           "manifest.json")
        pio.write_bytes(os.path.join(directory, rel), sub)
        shards.append({"manifest": rel, "crc": pio.crc32(sub),
                       "size": len(sub)})
    meta = {"config": _config_meta(engine.config),
            "num_shards": engine.num_shards,
            "nlist_global": int(engine.nlist_global),
            "rows_used": [int(r) for r in st.rows_used],
            "epoch": int(st.epoch),
            "n_tombstones": int(st.n_tombstones)}
    return segments, meta, shards


def save_snapshot(engine, directory: str, *, term: int | None = None,
                  wal_seq: int | None = None) -> dict:
    """Checkpoint ``engine`` into ``directory``; returns the new manifest.

    The (WAL position, state) pair is captured atomically under the
    engine's mutation lock — rotating the WAL first, so every record the
    snapshot folds in lives in files that GC may then delete. All segment
    bytes are written and fsync'd BEFORE the manifest atomically flips to
    the new snapshot; a crash anywhere in between recovers from the old
    manifest plus the intact WAL chain. Works on ``SearchEngine`` and
    ``ShardedEngine`` (per-shard manifests).

    Checkpoints are **delta snapshots**: segments whose bytes match the
    parent manifest's CRC+size are referenced from the parent instead of
    rewritten (the manifest records the ``parent`` name and per-checkpoint
    byte accounting under ``delta``; ``_gc`` keeps every snapshot
    directory the new manifest still references).

    ``term`` stamps the manifest with the replication fencing term
    (default: carry the previous manifest's term forward, 0 on a fresh
    directory). ``wal_seq`` overrides the recorded WAL position — only
    for engines WITHOUT an attached writer whose state is known to fold
    in exactly that prefix (the standby-promotion path, where the replica
    applied shipped records without logging them locally).
    """
    os.makedirs(directory, exist_ok=True)
    try:
        parent = read_manifest(directory)
    except (NoSnapshotError, CorruptSnapshotError):
        parent = None  # fresh (or unreadable) parent -> full snapshot
    with engine._mutate_lock:
        wal = getattr(engine, "_wal", None)
        if wal is not None:
            wal.rotate(directory)
            wal_seq = wal.last_seq
        elif wal_seq is None:
            wal_seq = 0
        st = engine._state  # immutable — safe to serialize outside the lock
    if term is None:
        term = 0 if parent is None else int(parent.get("term", 0))
    snap_name = _next_snap_name(directory)
    snap_dir = os.path.join(directory, snap_name)
    os.makedirs(snap_dir, exist_ok=True)
    stats = _DeltaStats()
    if isinstance(engine, ShardedEngine):
        segments, meta, shards = _serialize_sharded(
            engine, st, directory, snap_dir, parent, stats)
        kind = "sharded"
    else:
        segments, meta, shards = _serialize_single(
            engine, st, directory, snap_dir, parent, stats)
        kind = "single"
    # autotune verdicts ride along so a restored replica serves warm
    tmp = os.path.join(snap_dir, "autotune.tmp")
    ops_mod.save_autotune_cache(tmp)
    with open(tmp, "rb") as f:
        tune = f.read()
    os.remove(tmp)
    tune_crc = pio.crc32(tune)
    old_tune = None if parent is None else parent["segments"].get("autotune")
    if (old_tune is not None and old_tune.get("crc") == tune_crc
            and old_tune.get("size") == len(tune)
            and os.path.exists(os.path.join(directory, old_tune["file"]))):
        segments["autotune"] = {"file": old_tune["file"], "crc": tune_crc,
                                "size": len(tune)}
        stats.bytes_reused += len(tune)
        stats.segments_reused += 1
    else:
        rel = os.path.join(snap_name, "autotune.json")
        pio.write_bytes(os.path.join(directory, rel), tune)
        segments["autotune"] = {"file": rel, "crc": tune_crc,
                                "size": len(tune)}
        stats.bytes_written += len(tune)
        stats.segments_written += 1
    pio.fsync_dir(snap_dir)
    manifest = {"schema": SCHEMA, "kind": kind, "snapshot": snap_name,
                "term": int(term),
                "parent": None if parent is None else parent["snapshot"],
                "delta": stats.as_meta(),
                "wal_seq": int(wal_seq), "meta": meta, "segments": segments}
    if shards is not None:
        manifest["shards"] = shards
    manifest["manifest_crc"] = _manifest_crc(manifest)
    pio.atomic_write_bytes(os.path.join(directory, MANIFEST_NAME),
                           json.dumps(manifest, indent=1).encode("utf-8"))
    _gc(directory, manifest, wal_seq,
        keep=None if wal is None else wal.path)
    return manifest


def _reachable_snaps(manifest: dict) -> set[str]:
    """Snapshot directories the manifest still references — its own plus
    any parent dirs that delta entries point into (the live parent chain)."""
    rels = [e["file"] for e in manifest["segments"].values()]
    rels += [sh["manifest"] for sh in manifest.get("shards", ())]
    keep = {manifest["snapshot"]}
    for rel in rels:
        head = rel.replace(os.sep, "/").split("/", 1)[0]
        if head.startswith("snap-"):
            keep.add(head)
    return keep


def _gc(directory: str, manifest: dict, wal_seq: int,
        keep: str | None) -> None:
    """Drop snapshots and WAL files the new manifest supersedes.

    Runs only after the manifest is durable. A snapshot directory survives
    while ANY current segment references into it (the delta parent chain);
    note the per-shard sub-manifests live inside their snapshot directory,
    so a kept directory keeps its shard segment tables too — and those
    tables' own entries always point within the same directory set the top
    manifest references. A WAL file is deletable when a LATER file exists
    and every record it could hold is <= ``wal_seq`` (the final file's
    extent is unknown without a scan, so it always stays); the active
    writer's file is never touched.
    """
    reachable = _reachable_snaps(manifest)
    # shard sub-manifests referenced by the top manifest may in turn
    # reference parent shard directories: walk them too
    for sh in manifest.get("shards", ()):
        try:
            with open(os.path.join(directory, sh["manifest"])) as f:
                sub = json.load(f)
            for e in sub.get("segments", {}).values():
                head = e["file"].replace(os.sep, "/").split("/", 1)[0]
                if head.startswith("snap-"):
                    reachable.add(head)
        except (OSError, json.JSONDecodeError, KeyError):
            continue  # unreadable sub-manifest: keep GC conservative below
    for name in os.listdir(directory):
        if (name.startswith("snap-") and name not in reachable
                and os.path.isdir(os.path.join(directory, name))):
            shutil.rmtree(os.path.join(directory, name), ignore_errors=True)
    files = wal_mod.wal_files(directory)
    for i, (_start, path) in enumerate(files[:-1]):
        covered = files[i + 1][0] <= wal_seq + 1
        if covered and path != keep:
            try:
                os.remove(path)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# load + recovery
# ---------------------------------------------------------------------------

def _deserialize_single(directory: str, manifest: dict) -> SearchEngine:
    segs = manifest["segments"]
    meta = manifest["meta"]
    get = lambda name: _load_array(directory, segs[name], "snapshot")
    store_arrays = {k: get(k) for k in ("codes", "ids", "sizes")}
    if "attrs" in segs:
        store_arrays["attrs"] = get("attrs")
    index = ivf_mod.IVFIndex(
        centroids=jnp.asarray(get("centroids")),
        codebook=PQCodebook(jnp.asarray(get("codebook"))),
        lists=lists_mod.store_from_arrays(store_arrays))
    engine = SearchEngine(
        index,
        base=jnp.asarray(get("base")) if "base" in segs else None,
        coarse=meta["coarse_kind"],
        config=EngineConfig(**meta["config"]),
        hnsw_m=int(meta["hnsw_m"]),
        ef_construction=int(meta["ef_construction"]),
        namespaces=get("ns_member") if "ns_member" in segs else None)
    # the constructor recomputes the live bits (bitwise-equal by
    # construction); install the snapshotted ones + epoch verbatim anyway
    engine._state = engine._state._replace(
        live_bits=(jnp.asarray(get("live_bits"))
                   if "live_bits" in segs else None),
        epoch=int(meta["epoch"]),
        n_tombstones=int(meta["n_tombstones"]))
    return engine


def _deserialize_sharded(directory: str, manifest: dict) -> ShardedEngine:
    segs = manifest["segments"]
    meta = manifest["meta"]
    num_shards = int(meta["num_shards"])
    if len(manifest.get("shards", ())) != num_shards:
        raise CorruptSnapshotError(
            f"manifest lists {len(manifest.get('shards', ()))} shard "
            f"manifests but meta says num_shards={num_shards}")
    per_shard: list[dict[str, np.ndarray]] = []
    for entry in manifest["shards"]:
        sub_bytes = _read_verified(directory, {"file": entry["manifest"],
                                               "crc": entry["crc"],
                                               "size": entry["size"]},
                                   "shard manifest")
        try:
            sub = json.loads(sub_bytes.decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise CorruptSnapshotError(
                f"shard manifest {entry['manifest']} unparseable: {e}") from e
        per_shard.append({k: _load_array(directory, v, "shard snapshot")
                          for k, v in sub["segments"].items()})
    stack = lambda name: jnp.asarray(
        np.stack([sh[name] for sh in per_shard]))
    store_arrays = {k: np.stack([sh[k] for sh in per_shard])
                    for k in ("codes", "ids", "sizes")}
    if "attrs" in per_shard[0]:
        store_arrays["attrs"] = np.stack([sh["attrs"] for sh in per_shard])
    lists_s = lists_mod.store_from_arrays(store_arrays)
    has_base = "base" in per_shard[0]
    n_tomb = int(meta["n_tombstones"])
    engine = object.__new__(ShardedEngine)
    engine.num_shards = num_shards
    engine.codebook = PQCodebook(
        jnp.asarray(_load_array(directory, segs["codebook"], "snapshot")))
    engine.config = EngineConfig(**meta["config"])
    engine.centroids = jnp.asarray(
        _load_array(directory, segs["centroids"], "snapshot"))
    engine.nlist_global = int(meta["nlist_global"])
    engine.member_s = (
        jnp.asarray(_load_array(directory, segs["member_s"], "snapshot"),
                    bool) if "member_s" in segs else None)
    engine._state = _ShardState(
        centroids_s=stack("centroids"), lists_s=lists_s,
        real_s=stack("real").astype(bool),
        base_s=stack("base") if has_base else None,
        gids_s=stack("gids"),
        live_s=pack_filter_mask(lists_s.ids >= 0) if n_tomb else None,
        rows_used=tuple(int(r) for r in meta["rows_used"]),
        epoch=int(meta["epoch"]), n_tombstones=n_tomb)
    engine._mutate_lock = threading.RLock()
    engine._locator = None
    engine._wal = None
    engine._mesh = None
    return engine


def load_snapshot(directory: str):
    """(engine, manifest) from the last complete snapshot — NO WAL replay.

    The raw snapshot restore, exposed for tools and tests; serving boots
    through ``open_engine`` so acknowledged mutations past the snapshot
    are replayed too.
    """
    manifest = read_manifest(directory)
    if manifest["kind"] == "sharded":
        engine = _deserialize_sharded(directory, manifest)
    else:
        engine = _deserialize_single(directory, manifest)
    if "autotune" in manifest["segments"]:
        tune = _read_verified(directory, manifest["segments"]["autotune"],
                              "autotune")
        tmp = os.path.join(directory, ".autotune.load.tmp")
        with open(tmp, "wb") as f:
            f.write(tune)
        try:
            ops_mod.load_autotune_cache(tmp)
        finally:
            os.remove(tmp)
    return engine, manifest


def open_engine(directory: str, *, attach: bool = True):
    """Recover: last snapshot + WAL replay; returns (engine, RecoveryInfo).

    The recovered engine is bit-identical to the never-crashed engine over
    the acknowledged-mutation prefix the directory holds. A torn record at
    the tail of the FINAL WAL file — the signature of a crash mid-append —
    is truncated away (that mutation never acknowledged); any other damage
    raises ``CorruptSnapshotError``/``CorruptWALError`` instead of serving
    a silently wrong index. With ``attach=True`` (default) a fresh WAL
    writer is attached at the next sequence number, so the engine is
    immediately durable again.
    """
    engine, manifest = load_snapshot(directory)
    wal_seq = int(manifest["wal_seq"])
    truncated = 0
    files = wal_mod.wal_files(directory)
    if files:
        last_path = files[-1][1]
        _, valid, clean = wal_mod.scan_wal(last_path)
        if not clean:
            truncated = os.path.getsize(last_path) - valid
            with open(last_path, "r+b") as f:
                f.truncate(valid)
                f.flush()
                os.fsync(f.fileno())
    replayed = 0
    for rec in wal_mod.iter_wal(directory, after_seq=wal_seq):
        wal_mod.apply_record(engine, rec)
        replayed += 1
    last_seq = wal_seq + replayed
    term = int(manifest.get("term", 0))
    if attach:
        writer = wal_mod.WALWriter(
            os.path.join(directory, wal_mod.wal_name(last_seq + 1)),
            last_seq + 1, term=term)
        engine.attach_wal(writer)
    return engine, RecoveryInfo(snapshot=manifest["snapshot"],
                                wal_seq=wal_seq, replayed=replayed,
                                last_seq=last_seq,
                                truncated_bytes=truncated,
                                term=term)


def ensure_attached(engine, directory: str) -> None:
    """Boot contract for serving: make ``engine`` durable into ``directory``.

    Fresh directory -> write the initial snapshot and attach a WAL writer
    at seq 1. Already attached to this directory (the ``open_engine``
    path) -> no-op. A directory that already holds a manifest the engine
    did NOT come from is refused: silently re-initializing would fork the
    history and orphan acknowledged mutations.
    """
    os.makedirs(directory, exist_ok=True)
    wal = getattr(engine, "_wal", None)
    if wal is not None and (os.path.dirname(os.path.abspath(wal.path))
                            == os.path.abspath(directory)):
        return
    try:
        read_manifest(directory)
    except NoSnapshotError:
        manifest = save_snapshot(engine, directory)
        writer = wal_mod.WALWriter(
            os.path.join(directory, wal_mod.wal_name(1)), 1,
            term=int(manifest.get("term", 0)))
        engine.attach_wal(writer)
        return
    raise ValueError(
        f"{directory} already holds a durable index this engine did not "
        "come from — boot it with persist.open_engine(directory) so the "
        "WAL resumes where it left off")
