"""Snapshot integrity: content digests, manifests, quarantine, fsck.

The restart loop (launch.py supervisor, CLI ``--retries``, graceful
preemption) trusts that the latest orbax snapshot is intact — but saves
are ASYNC and restarts are triggered by SIGKILL-class events (stall
watchdog, chaos ``crash``, OOM, hard preemption deadlines), so a step
directory can be torn mid-write, and long-lived sweep state can bit-rot.
A poisoned latest step turns "free restart" into a crash loop that burns
the whole retry/preemption budget re-reading the same bad bytes.

This module is the bounding layer:

- **Verified saves**: ``build_manifest`` computes per-item content
  digests at save time; both checkpointers write the manifest as an
  extra JSON item inside the same orbax step. ``verify_restored``
  recomputes digests from the restored values before any state is
  applied.
- **Sealed steps**: the item digests prove what a restore RETURNS; they
  say nothing of the files a restore never decodes, and the installed
  orbax logs and tolerates a mangled ``_CHECKPOINT_METADATA`` (a
  truncated step restored as if whole). So once a step has committed,
  ``seal_step`` writes a file-level manifest (``_SEAL``: size + SHA-256
  of every file of the step, itself self-digested) and ``check_seal``
  re-hashes the files BEFORE orbax is asked to decode anything. "Is
  this step whole" is decided by this repo's own digests, not by
  whether orbax raises. A step killed between its commit and its seal
  carries no ``_SEAL`` and falls back to the item digests alone.
- **Quarantine**: a step that fails restore or digest verification is
  renamed ``<step>.corrupt`` (never deleted — it is evidence), an
  observer event ``snapshot_corrupt`` fires (the CLI wires it into the
  metrics stream + ``snapshots_quarantined`` counter), and restore walks
  back to the newest older retained step (``keep`` is the fallback
  budget). Only when NO verified step remains does restore raise
  ``NoVerifiedSnapshotError`` — which the CLI maps to exit
  ``EX_DATAERR`` (65), the one failure class a supervisor must NOT
  retry: every restart would re-read the same dead state.
- **fsck**: ``mpi_opt_tpu fsck <dir>`` audits a sweep's durable state
  offline — enumerates steps, verifies manifests, cross-checks a
  co-located ledger journal against the newest verified snapshot
  (trial-granular for driver ledgers, boundary-granular for fused
  ones: every boundary a snapshot records complete must be fully
  journaled), ``--repair`` quarantines bad steps, ``--deep``
  additionally reads back every ocdbt key so tensorstore's CRC-32C
  checksums audit bytes a restore never touches; ``--json`` +
  exit-code contract for CI, mirroring ``report --validate``.

Digest notes: leaves are hashed as (path, dtype, shape, bytes) via
SHA-256, path-sorted so the flax-dataclass-vs-plain-dict structure
difference orbax's round trip introduces cannot flip the order. JSON
items are canonicalized through one json round trip (tuples become
lists, int keys become strings) so the save-side digest matches the
restored side byte-for-byte. Digesting a device-resident pool costs one
synchronous host fetch at save time — the price of knowing the bytes
you wrote are the bytes you'll read. Non-fully-addressable (multi-host
sharded) leaves are recorded as unverifiable and skipped on verify.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Callable, Optional

# EX_DATAERR re-export (utils/exitcodes.py is the one home for the
# values; the historical `utils.integrity.EX_DATAERR` surface stays):
# the exit code for "resume found snapshots but none verified" — the
# one failure a launch supervisor must classify as NON-retryable (a
# restart re-reads the same poisoned state; see launch.py).
from mpi_opt_tpu.utils.exitcodes import EX_DATAERR  # noqa: F401

MANIFEST_ITEM = "manifest"
MANIFEST_VERSION = 1

# item names both checkpointers save as JSON (everything else is an
# array tree); fsck uses this to pick restore handlers for legacy steps
# that predate the manifest
_JSON_ITEMS = ("search", "meta", MANIFEST_ITEM)


class SnapshotCorruptError(RuntimeError):
    """One snapshot step failed restore/decode or digest verification
    (internal to the walk-back; callers see quarantine + fallback)."""


class NoVerifiedSnapshotError(RuntimeError):
    """Resume found snapshot steps but NONE verified: every retained
    step was quarantined. Restarting cannot help — the CLI exits
    ``EX_DATAERR`` and the launch supervisor aborts with diagnostics
    instead of consuming its retry/preemption budget."""

    def __init__(self, directory: str, quarantined: list):
        self.directory = directory
        self.quarantined = list(quarantined)
        super().__init__(
            f"no verified snapshot remains under {directory}: "
            f"{len(self.quarantined)} step(s) failed verification and were "
            f"quarantined ({', '.join(os.path.basename(q) for q in self.quarantined)}). "
            "Inspect the *.corrupt directories (mpi_opt_tpu fsck), then "
            "restart WITHOUT --resume to start fresh, or point at a "
            "different --checkpoint-dir. (Every retained step failing at "
            "once can also mean software drift — an orbax/schema upgrade "
            "— rather than bit-rot; the renames are reversible, so after "
            "fixing the environment the steps can be renamed back)"
        )


# -- digests ----------------------------------------------------------------


def _path_names(path) -> tuple:
    """A key path as bare name strings, normalized across node kinds:
    GetAttrKey('params') (flax dataclass) and DictKey('params') (the
    plain dict orbax restores it as) both become 'params', so save-side
    and restore-side digests see the same ordering."""
    out = []
    for p in path:
        for attr in ("name", "key", "idx"):
            v = getattr(p, attr, None)
            if v is not None:
                out.append(str(v))
                break
        else:
            out.append(str(p))
    return tuple(out)


def _leaf_digest(leaf) -> Optional[str]:
    """SHA-256 over (dtype, shape, bytes) of one array leaf; None when
    the leaf's bytes aren't reachable from this process (a non-fully-
    addressable multi-host shard) — recorded as unverifiable."""
    import numpy as np

    try:
        import jax

        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            return None
    except Exception:
        pass
    arr = np.asarray(leaf)
    h = hashlib.sha256()
    h.update(str(arr.dtype).encode())
    h.update(str(arr.shape).encode())
    h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


# total tree bytes above which leaf (= shard) hashing fans out across a
# thread pool: hashlib releases the GIL for buffers >= 2048 bytes, so a
# multi-GB pool's per-shard digests run genuinely parallel on multi-core
# hosts instead of serially on the save hot path. Workers clamp to the
# core count — on this 1-core container the path measures cost-neutral
# at 0.62 GB/s (PERF_NOTES round 6); the win scales with cores. Small
# trees stay serial — pool spin-up would cost more than it saves.
_PARALLEL_DIGEST_BYTES = int(
    os.environ.get("MPI_OPT_TPU_DIGEST_PARALLEL_BYTES", 64 << 20)
)


def _leaf_nbytes(leaf) -> int:
    try:
        import numpy as np

        return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    except Exception:
        return 0


def tree_digest(tree) -> Optional[str]:
    """Content digest of an array pytree, stable across the
    dataclass->dict structure change orbax's round trip introduces
    (leaves are path-sorted by normalized key names). None when any
    leaf is unverifiable from this process.

    Large trees (>= ``MPI_OPT_TPU_DIGEST_PARALLEL_BYTES``, default
    64 MiB) hash their leaves on a thread pool — per-shard, off the
    caller's hot thread — so a multi-GB pool's save-side digest costs
    roughly one shard's wall, not the sum. The combined digest is
    order-identical to the serial path (per-leaf digests are combined
    in sorted path order), so snapshots written either way verify
    against each other."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    entries = sorted((( _path_names(p), l) for p, l in flat), key=lambda e: e[0])
    leaves = [l for _, l in entries]
    if (
        len(leaves) > 1
        and sum(_leaf_nbytes(l) for l in leaves) >= _PARALLEL_DIGEST_BYTES
    ):
        from concurrent.futures import ThreadPoolExecutor

        workers = min(8, os.cpu_count() or 1, len(leaves))
        with ThreadPoolExecutor(max_workers=workers) as ex:
            digests = list(ex.map(_leaf_digest, leaves))
    else:
        digests = [_leaf_digest(l) for l in leaves]
    h = hashlib.sha256()
    for (path, _leaf), d in zip(entries, digests):
        if d is None:
            return None
        h.update("/".join(path).encode())
        h.update(d.encode())
    return h.hexdigest()


def json_digest(obj) -> str:
    """Digest of a JSON-item value, canonicalized through one json
    round trip so pre-serialization quirks (tuples, int keys) hash the
    same as the restored value."""
    canonical = json.loads(json.dumps(obj))
    return hashlib.sha256(
        json.dumps(canonical, sort_keys=True).encode()
    ).hexdigest()


def build_manifest(json_items: dict, tree_items: dict) -> dict:
    """The manifest record saved alongside a step's items:
    ``{"version", "items": {name: {"kind": "json"|"tree", "digest"}}}``.
    A ``digest`` of None marks an item unverifiable at save time
    (multi-host shards); verify skips it rather than failing."""
    from mpi_opt_tpu.obs import trace

    with trace.span("digest", op="build", items=len(json_items) + len(tree_items)):
        items = {}
        for name, val in json_items.items():
            items[name] = {"kind": "json", "digest": json_digest(val)}
        for name, val in tree_items.items():
            items[name] = {"kind": "tree", "digest": tree_digest(val)}
        return {"version": MANIFEST_VERSION, "items": items}


def verify_restored(manifest: dict, json_items: dict, tree_items: dict) -> list:
    """Recompute digests of restored values against ``manifest``;
    returns human-readable problems (empty = verified). Items the
    manifest lists but the caller didn't restore are problems too — a
    vanished item is exactly the torn-save shape."""
    from mpi_opt_tpu.obs import trace

    problems = []
    recorded = manifest.get("items", {})
    restored = {**json_items, **tree_items}
    with trace.span("digest", op="verify", items=len(recorded)):
        for name, entry in recorded.items():
            want = entry.get("digest")
            if want is None:
                continue  # unverifiable at save time (multi-host shard)
            if name not in restored:
                problems.append(
                    f"item {name!r}: recorded in manifest but not restored"
                )
                continue
            got = (
                json_digest(restored[name])
                if entry.get("kind") == "json"
                else tree_digest(restored[name])
            )
            if got != want:
                problems.append(
                    f"item {name!r}: content digest mismatch "
                    f"(saved {want[:12]}..., restored {(got or 'unverifiable')[:12]}...)"
                )
        for name in restored:
            if name not in recorded:
                problems.append(f"item {name!r}: present but not in manifest")
    return problems


# -- sealed steps -----------------------------------------------------------

SEAL_FILE = "_SEAL"
SEAL_VERSION = 1
#: orbax's step metadata file: present once a step has committed
COMMIT_MARKER = "_CHECKPOINT_METADATA"


def _file_digest(path: str) -> tuple:
    """(size, SHA-256 hex) of one file, read in 1 MiB blocks."""
    h = hashlib.sha256()
    size = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(1 << 20)
            if not block:
                break
            size += len(block)
            h.update(block)
    return size, h.hexdigest()


def _step_files(step_dir: str) -> list:
    """Every regular file of a step except the seal, as sorted
    ``/``-separated paths relative to the step dir."""
    out = []
    for root, _dirs, files in os.walk(step_dir):
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), step_dir)
            if rel != SEAL_FILE:
                out.append(rel.replace(os.sep, "/"))
    return sorted(out)


def _hash_files(step_dir: str, rels: list) -> dict:
    """``{rel: {"size", "sha256"}}``; hashed on a thread pool (hashlib
    releases the GIL), since a ResNet pool's step is gigabytes."""
    from concurrent.futures import ThreadPoolExecutor

    paths = [os.path.join(step_dir, *rel.split("/")) for rel in rels]
    workers = max(1, min(8, os.cpu_count() or 1, len(paths)))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        digests = list(ex.map(_file_digest, paths))
    return {rel: {"size": n, "sha256": d} for rel, (n, d) in zip(rels, digests)}


def seal_step(step_dir: str) -> None:
    """Write ``<step_dir>/_SEAL`` for a COMMITTED step: one JSON line
    listing every file with its size and SHA-256, then a second line
    holding the SHA-256 of the first — so no byte of the seal can change
    unnoticed either. Written tmp + fsync + rename: a kill mid-seal
    leaves no seal, never half of one."""
    from mpi_opt_tpu.obs import trace

    rels = _step_files(step_dir)
    with trace.span("digest", op="seal", items=len(rels)):
        body = json.dumps(
            {"version": SEAL_VERSION, "files": _hash_files(step_dir, rels)},
            sort_keys=True,
            separators=(",", ":"),
        ).encode()
        path = os.path.join(step_dir, SEAL_FILE)
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            f.write(body + b"\n" + hashlib.sha256(body).hexdigest().encode() + b"\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)


def check_seal(step_dir: str) -> Optional[list]:
    """None when the step carries no seal (killed between commit and
    seal, or written before seals existed); otherwise the problems
    found re-hashing every file against it (empty = the step is whole)."""
    from mpi_opt_tpu.obs import trace

    path = os.path.join(step_dir, SEAL_FILE)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except FileNotFoundError:
        return None
    body, _, tail = raw.partition(b"\n")
    if tail != hashlib.sha256(body).hexdigest().encode() + b"\n":
        return [f"{SEAL_FILE}: self-digest mismatch (torn or altered seal)"]
    try:
        doc = json.loads(body)
        recorded = doc["files"]
        if doc["version"] != SEAL_VERSION or not isinstance(recorded, dict):
            raise ValueError(f"unknown seal version {doc['version']!r}")
    except (ValueError, KeyError, TypeError) as e:
        return [f"{SEAL_FILE}: unreadable ({type(e).__name__}: {e})"]
    present = _step_files(step_dir)
    problems = [f"file {rel!r}: sealed but missing" for rel in recorded if rel not in present]
    problems += [f"file {rel!r}: present but not sealed" for rel in present if rel not in recorded]
    both = [rel for rel in present if rel in recorded]
    with trace.span("digest", op="check_seal", items=len(both)):
        got = _hash_files(step_dir, both)
    for rel in both:
        if got[rel] != recorded[rel]:
            problems.append(
                f"file {rel!r}: content changed since the step was sealed "
                f"(size {recorded[rel].get('size')} -> {got[rel]['size']})"
            )
    return problems


# -- quarantine -------------------------------------------------------------


def quarantine_step(directory: str, step: int) -> Optional[str]:
    """Rename ``<directory>/<step>`` to ``<step>.corrupt`` (never
    delete: the bytes are evidence). Returns the quarantine path, or
    None when the step dir no longer exists. A name collision from a
    previous quarantine gets a numeric suffix."""
    src = os.path.join(directory, str(step))
    if not os.path.isdir(src):
        return None
    dst = f"{src}.corrupt"
    n = 1
    while os.path.exists(dst):
        dst = f"{src}.corrupt.{n}"
        n += 1
    os.replace(src, dst)
    return dst


def list_quarantined(directory: str) -> list:
    """Quarantined step dirs under ``directory`` (recursive: hyperband
    brackets nest per-bracket checkpoint roots)."""
    out = []
    for root, dirs, _files in os.walk(directory):
        for d in dirs:
            base = d.split(".corrupt")[0]
            if d != base and base.isdigit() and d[len(base):].startswith(".corrupt"):
                out.append(os.path.join(root, d))
    return sorted(out)


# -- corruption observer ----------------------------------------------------
#
# checkpoint.py has no metrics handle (fused trainers build their own
# checkpointers deep inside the sweep), so corruption events flow
# through a process-wide observer the CLI wires to its MetricsLogger —
# the same module-global pattern as health.heartbeat.

_OBSERVER: Optional[Callable] = None


def set_observer(cb: Optional[Callable]) -> None:
    """Install ``cb(event, **fields)`` as the corruption-event sink
    (the CLI points this at metrics.log + the quarantine counter)."""
    global _OBSERVER
    _OBSERVER = cb


def clear_observer() -> None:
    set_observer(None)


def notify(event: str, **fields) -> None:
    """Report a corruption-layer event; falls back to a warning so a
    library caller (tests, embedders) still sees quarantines happen."""
    if _OBSERVER is not None:
        _OBSERVER(event, **fields)
        return
    import warnings

    warnings.warn(f"{event}: {fields}", RuntimeWarning, stacklevel=2)


# -- fsck -------------------------------------------------------------------


def _committed_steps(root: str) -> list:
    """Numeric step dirs under ``root`` that carry the orbax commit
    marker, sorted ascending."""
    out = []
    for d in os.listdir(root):
        if d.isdigit() and os.path.exists(os.path.join(root, d, COMMIT_MARKER)):
            out.append(int(d))
    return sorted(out)


def _torn_steps(root: str) -> list:
    """Numeric step dirs WITHOUT the commit marker: a save that never
    committed (killed mid-async-write). orbax itself ignores them; fsck
    surfaces them so --repair can quarantine the debris."""
    out = []
    for d in os.listdir(root):
        if d.isdigit() and not os.path.exists(os.path.join(root, d, COMMIT_MARKER)):
            out.append(int(d))
    return sorted(out)


def find_checkpoint_roots(directory: str) -> list:
    """Directories under ``directory`` (inclusive) that directly hold
    step dirs — one root for flat sweeps, one per bracket dir for
    hyperband."""
    roots = []
    for root, dirs, _files in os.walk(directory):
        if any(d.isdigit() for d in dirs) or any(".corrupt" in d for d in dirs):
            roots.append(root)
            # don't descend into the step dirs themselves
            dirs[:] = [d for d in dirs if not (d.split(".")[0].isdigit())]
    return sorted(roots)


def verify_step(root: str, step: int, mgr=None) -> tuple:
    """(status, problems) for one committed step: ``"verified"`` (every
    manifest digest matches), ``"legacy"`` (pre-manifest step — decodes
    but can't be content-verified), or ``"corrupt"``. Pass ``mgr`` (an
    open CheckpointManager on ``root``) to amortize the per-root scan
    over many steps — fsck does."""
    import orbax.checkpoint as ocp

    step_dir = os.path.join(root, str(step))
    names = sorted(
        d for d in os.listdir(step_dir)
        if os.path.isdir(os.path.join(step_dir, d))
    )
    seal_problems = check_seal(step_dir)
    if seal_problems:
        return "corrupt", seal_problems
    own_mgr = mgr is None
    if own_mgr:
        mgr = ocp.CheckpointManager(root)
    try:
        if MANIFEST_ITEM in names:
            try:
                manifest = mgr.restore(
                    step,
                    args=ocp.args.Composite(
                        **{MANIFEST_ITEM: ocp.args.JsonRestore()}
                    ),
                )[MANIFEST_ITEM]
            except Exception as e:
                return "corrupt", [f"manifest unreadable: {type(e).__name__}: {e}"]
            kinds = {
                n: e.get("kind", "tree")
                for n, e in manifest.get("items", {}).items()
            }
        else:
            manifest = None
            kinds = {
                n: ("json" if n in _JSON_ITEMS else "tree")
                for n in names
            }
        args = {}
        for n in names:
            if n == MANIFEST_ITEM:
                continue
            args[n] = (
                ocp.args.JsonRestore()
                if kinds.get(n, "tree") == "json"
                else ocp.args.StandardRestore()
            )
        try:
            r = mgr.restore(step, args=ocp.args.Composite(**args))
        except Exception as e:
            return "corrupt", [f"restore failed: {type(e).__name__}: {e}"]
        if manifest is None:
            return "legacy", ["no integrity manifest (pre-upgrade step)"]
        json_items = {n: r[n] for n in args if kinds.get(n) == "json"}
        tree_items = {n: r[n] for n in args if kinds.get(n) != "json"}
        problems = verify_restored(manifest, json_items, tree_items)
        return ("verified", []) if not problems else ("corrupt", problems)
    finally:
        if own_mgr:
            mgr.close()


def deep_verify_step(root: str, step: int) -> list:
    """``fsck --deep``: ocdbt-internal checksum audit of one committed
    step. Opens every ocdbt database under the step dir (orbax writes a
    top-level store per item PLUS nested ``ocdbt.process_*`` stores)
    and reads EVERY key back — tensorstore validates its CRC-32C
    checksums on read, so rot inside b-tree nodes or data files
    surfaces here even when it hides from a normal restore: measured in
    this container, a bit-flip in a nested process store's data file
    reads back clean through the top-level database (the item-digest
    layer verifies what a restore RETURNS, not every byte on disk; the
    seal covers every byte, but a step killed between commit and seal
    has none). Returns problems (empty = every stored byte decoded
    clean).
    """
    problems: list = []
    try:
        import tensorstore as ts
    except Exception as e:  # the orbax dep should always carry it
        return [f"--deep unavailable: tensorstore import failed ({e})"]
    step_dir = os.path.join(root, str(step))
    for dirpath, _dirnames, filenames in os.walk(step_dir):
        if "manifest.ocdbt" not in filenames:
            continue
        rel = os.path.relpath(dirpath, step_dir)
        try:
            kv = ts.KvStore.open(
                {"driver": "ocdbt", "base": {"driver": "file", "path": dirpath}}
            ).result()
            for key in kv.list().result():
                kv.read(key).result()
        except Exception as e:
            problems.append(
                f"ocdbt {rel}: {type(e).__name__}: {str(e)[:300]}"
            )
    return problems


def load_sweep_meta(root: str, step: int, mgr=None) -> Optional[dict]:
    """The ``meta`` JSON item of a FUSED sweep's step (None when the
    step holds none — driver-path steps save ``search``/``pool``).
    fsck's fused ledger cross-check reads ``boundaries_done`` from it."""
    import orbax.checkpoint as ocp

    step_dir = os.path.join(root, str(step))
    if not os.path.isdir(os.path.join(step_dir, "meta")):
        return None
    own_mgr = mgr is None
    if own_mgr:
        mgr = ocp.CheckpointManager(root)
    try:
        return mgr.restore(
            step, args=ocp.args.Composite(meta=ocp.args.JsonRestore())
        )["meta"]
    finally:
        if own_mgr:
            mgr.close()


def load_search_state(root: str, step: int, mgr=None) -> Optional[dict]:
    """The ``search`` JSON item of a step, or None when the step holds
    no driver-path search state (fused sweeps save ``sweep``/``meta``)."""
    import orbax.checkpoint as ocp

    step_dir = os.path.join(root, str(step))
    if not os.path.isdir(os.path.join(step_dir, "search")):
        return None
    own_mgr = mgr is None
    if own_mgr:
        mgr = ocp.CheckpointManager(root)
    try:
        return mgr.restore(
            step, args=ocp.args.Composite(search=ocp.args.JsonRestore())
        )["search"]
    finally:
        if own_mgr:
            mgr.close()


def _sniffs_as_ledger(path: str) -> bool:
    """Does line 1 look like a ledger header? (fsck's auto-detect gate;
    the sniff itself has one home, ``ledger.store.sniff_header``)"""
    from mpi_opt_tpu.ledger.store import sniff_header

    return sniff_header(path) is not None


def _sniffs_as_fused_ledger(path: str) -> bool:
    """Was this ledger written by a fused sweep? (picks which replay
    cross-check fsck runs: boundary-granular vs trial-granular)"""
    from mpi_opt_tpu.ledger.store import sniff_header

    header = sniff_header(path)
    return header is not None and header.get("config", {}).get("mode") == "fused"


def fsck_main(argv=None) -> int:
    """The ``mpi_opt_tpu fsck`` subcommand (see cli.main dispatch).

    Exit 0: every committed step verified (or legacy). Exit 1: any
    corrupt or torn step found this run (with ``--repair`` they are
    quarantined, but the run still reports the corruption it found —
    CI distinguishes "clean" from "repaired"). Usage errors exit 2.
    """
    import argparse

    p = argparse.ArgumentParser(
        prog="mpi_opt_tpu fsck",
        description="audit a sweep's durable checkpoint state: verify "
        "snapshot manifests, surface torn saves, cross-check a ledger "
        "journal (see README: snapshot integrity)",
    )
    p.add_argument("directory", metavar="DIR", help="checkpoint directory")
    p.add_argument(
        "--repair",
        action="store_true",
        help="quarantine corrupt/torn steps (rename to <step>.corrupt) "
        "so a subsequent --resume restores the newest verified step",
    )
    p.add_argument(
        "--deep",
        action="store_true",
        help="additionally read back every key of every ocdbt database "
        "inside each committed step (tensorstore validates its CRC-32C "
        "checksums on read) — catches rot in ocdbt-internal structures "
        "a normal restore never touches; slower (full re-read)",
    )
    p.add_argument("--json", action="store_true", help="machine-readable output")
    p.add_argument(
        "--ledger",
        default=None,
        metavar="PATH",
        help="cross-check this ledger journal against the newest "
        "verified snapshot (default: any single co-located *.jsonl "
        "next to DIR's steps)",
    )
    args = p.parse_args(argv)
    directory = os.path.abspath(args.directory)
    if not os.path.isdir(directory):
        p.error(f"{args.directory!r} is not a directory")

    import orbax.checkpoint as ocp

    steps_out = []
    repaired = []
    newest_verified = None  # (root, step, mgr is closed by then — path only)
    newest_by_root: dict = {}  # root -> newest verified step (fused x-check)
    rc = 0
    for root in find_checkpoint_roots(directory):
        rel = os.path.relpath(root, directory)
        for step in _torn_steps(root):
            rc = 1
            entry = {
                "root": rel,
                "step": step,
                "status": "torn",
                "problems": ["uncommitted save (no _CHECKPOINT_METADATA)"],
            }
            if args.repair:
                q = quarantine_step(root, step)
                if q:
                    repaired.append(q)
                    entry["quarantined_to"] = os.path.basename(q)
            steps_out.append(entry)
        mgr = ocp.CheckpointManager(root)  # one scan amortized over steps
        try:
            for step in _committed_steps(root):
                status, problems = verify_step(root, step, mgr=mgr)
                if args.deep and status != "corrupt":
                    # ocdbt-internal audit on top of the manifest layer:
                    # a step whose restore verifies can still hold
                    # rotten bytes in stores a restore never reads
                    deep_problems = deep_verify_step(root, step)
                    if deep_problems:
                        status = "corrupt"
                        problems = problems + deep_problems
                entry = {
                    "root": rel, "step": step, "status": status, "problems": problems,
                }
                if status == "corrupt":
                    rc = 1
                    if args.repair:
                        q = quarantine_step(root, step)
                        if q:
                            repaired.append(q)
                            entry["quarantined_to"] = os.path.basename(q)
                elif status == "verified":
                    if newest_verified is None or step > newest_verified[1]:
                        newest_verified = (root, step)
                    if step > newest_by_root.get(root, -1):
                        newest_by_root[root] = step
                steps_out.append(entry)
        finally:
            mgr.close()

    # ledger audit: an explicit --ledger gets the full treatment (schema
    # + replay cross-check against the newest verified snapshot). With
    # no flag, exactly one co-located sibling jsonl that sniffs as a
    # ledger (header on line 1 — a metrics file also ends .jsonl) gets
    # the SCHEMA check only: auto-detection cannot prove the sibling
    # belongs to THIS sweep, and cross-checking a neighbor sweep's
    # journal would fail CI on a perfectly healthy tree.
    ledger_path = args.ledger
    explicit = ledger_path is not None
    if ledger_path is None:
        parent = os.path.dirname(directory) or "."
        sibling = [
            os.path.join(parent, f)
            for f in sorted(os.listdir(parent))
            if f.endswith(".jsonl")
            and _sniffs_as_ledger(os.path.join(parent, f))
        ]
        if len(sibling) == 1:
            ledger_path = sibling[0]
    ledger_out = None
    if ledger_path is not None:
        from mpi_opt_tpu.ledger.report import replay_consistency
        from mpi_opt_tpu.ledger.store import (
            LedgerError,
            SweepLedger,
            read_ledger,
            validate_ledger,
        )

        problems = validate_ledger(ledger_path)
        torn_tail = False
        torn_boundary = None
        if problems:
            # the two recoverable damage shapes a kill can leave: a torn
            # FINAL line (died mid-append) and, for fused journals, a
            # torn FINAL boundary (died between a boundary's member
            # records). The resume path self-heals both (SweepLedger
            # truncates on load); --repair does the same here so the
            # documented flag -> repair -> resume -> clean cycle also
            # goes green for ledgers, not just snapshot steps.
            try:
                _h, recs, n_torn = read_ledger(ledger_path, strict=False)
                torn_tail = n_torn > 0
                from mpi_opt_tpu.ledger.store import scan_boundaries

                _by, _sz, _bp, torn_boundary = scan_boundaries(recs)
            except Exception:
                torn_tail, torn_boundary = False, None
            if (torn_tail or torn_boundary is not None) and args.repair:
                try:
                    # sweeplint: disable=ledger-gate -- fsck --repair is a single-process operator tool; the load-time truncation IS the repair, no SPMD rank can race it
                    SweepLedger(ledger_path).close()  # load truncates in place
                except LedgerError:
                    pass  # damage beyond the append-kill shapes: report only
                else:
                    what = []
                    if torn_tail:
                        what.append("torn tail")
                    if torn_boundary is not None:
                        what.append(f"torn boundary {torn_boundary}")
                    repaired.append(f"{ledger_path} ({' + '.join(what)} truncated)")
                    problems = validate_ledger(ledger_path)
        if explicit and not problems:
            if _sniffs_as_fused_ledger(ledger_path):
                # boundary-granular invariant: every boundary any root's
                # newest verified snapshot records complete must be
                # fully journaled. MAX across roots — hyperband brackets
                # snapshot independently but share one global boundary
                # sequence, and the furthest-ahead bracket binds
                from mpi_opt_tpu.ledger.report import fused_replay_consistency

                done = [
                    int(meta["boundaries_done"])
                    for root, step in newest_by_root.items()
                    for meta in [load_sweep_meta(root, step)]
                    if meta is not None and "boundaries_done" in meta
                ]
                if done:
                    problems += fused_replay_consistency(ledger_path, max(done))
            else:
                search = (
                    load_search_state(*newest_verified) if newest_verified else None
                )
                if search is not None:
                    problems += replay_consistency(ledger_path, search)
        ledger_out = {
            "path": ledger_path,
            "problems": problems,
            "torn_tail": torn_tail,
            "torn_boundary": torn_boundary,
            "cross_checked": explicit,
        }
        # an auto-detected sibling can't be PROVEN to belong to this
        # sweep: its problems are reported but only an explicit --ledger
        # fails the audit (a neighbor sweep's torn journal must not turn
        # this tree's CI red). A repaired torn tail/boundary still
        # counts as damage FOUND this run, matching the step contract.
        if (problems or torn_tail or torn_boundary is not None) and explicit:
            rc = 1

    report = {
        "dir": directory,
        "ok": rc == 0,
        "steps": steps_out,
        "newest_verified": None if newest_verified is None else {
            "root": os.path.relpath(newest_verified[0], directory),
            "step": newest_verified[1],
        },
        "repaired": [os.path.basename(q) for q in repaired],
        "quarantined": [
            os.path.relpath(q, directory) for q in list_quarantined(directory)
        ],
        "ledger": ledger_out,
    }
    if args.json:
        print(json.dumps(report))
        return rc
    print(f"fsck {directory}: {'ok' if rc == 0 else 'CORRUPTION FOUND'}")
    for e in steps_out:
        loc = f"{e['root']}/{e['step']}" if e["root"] != "." else str(e["step"])
        line = f"  step {loc}: {e['status']}"
        if e["problems"]:
            line += f" ({'; '.join(e['problems'])})"
        if e.get("quarantined_to"):
            line += f" -> quarantined as {e['quarantined_to']}"
        print(line)
    if report["quarantined"]:
        print(f"  quarantined: {', '.join(report['quarantined'])}")
    if ledger_out is not None:
        status = "ok" if not ledger_out["problems"] else "; ".join(ledger_out["problems"])
        print(f"  ledger {ledger_out['path']}: {status}")
    if rc and not args.repair:
        print("  (re-run with --repair to quarantine bad steps, then --resume)")
    return rc
