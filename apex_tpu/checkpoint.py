"""Sharded checkpoint / resume for the whole training state.

The reference's checkpoint story (README.md "Checkpointing", lines 57-97)
is a dict convention: save ``model.state_dict()`` (fp32 via the O2 hook),
``optimizer.state_dict()`` and ``amp.state_dict()``, restore them after
re-running ``amp.initialize``. Its only distributed-state handling is
gather-to-rank-0 (DistributedFusedAdam's gathered ``state_dict`` —
contrib/optimizers/distributed_fused_adam.py); there is no sharded
checkpoint format anywhere in the tree.

The TPU build keeps the same three-part recipe — (params, opt_state, amp
state) as one pytree — and upgrades the mechanism to Orbax: every host
writes exactly its own shards (no gather), restore places each array
straight onto its mesh sharding from an abstract template, and a manager
handles retention/step discovery for resume. ZeRO-sharded optimizer
state (contrib DistributedFusedAdam/LAMB) round-trips without ever being
gathered — the capability the reference lacks.

Single-host multi-device and multi-host (``jax.distributed``) use the
same code path; Orbax coordinates the multi-host commit protocol.

On top of the Orbax layer sits the DURABILITY layer (ISSUE 6): a
crash-safe writer (:class:`DurableCheckpointer`) whose commits are
atomic (tmp + rename + content-hash manifest), whose restores walk
backward past torn/corrupt/stale files, and whose saves can run on a
background thread off the step critical path (``APEX_CKPT_ASYNC``;
default SYNC until the overhead A/B lands — the measured-dispatch
rule). Whatever a run computed must survive the kill that follows
it. The format is self-contained (numpy bytes + JSON manifest, no
orbax dependency) so an emergency restore never depends on the
optional stack.
"""

import hashlib
import json
import os
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

try:  # orbax is in the baked image; degrade gracefully elsewhere
    import orbax.checkpoint as ocp
    HAVE_ORBAX = True
except Exception:  # pragma: no cover
    ocp = None
    HAVE_ORBAX = False


def _require_orbax():
    if not HAVE_ORBAX:
        raise ImportError(
            "apex_tpu.checkpoint requires orbax-checkpoint; install it or "
            "use the in-memory amp.state_dict()/load_state_dict() recipe")


_PYTREE_PARTIAL = None


def _pytree_restore_supports_partial():
    """Feature-detect ``ocp.args.PyTreeRestore(partial_restore=...)`` —
    absent in the container's orbax 0.7.x (ISSUE 6 satellite); callers
    fall back to a full-tree restore + post-filter."""
    global _PYTREE_PARTIAL
    if _PYTREE_PARTIAL is None:
        import inspect

        try:
            _PYTREE_PARTIAL = "partial_restore" in inspect.signature(
                ocp.args.PyTreeRestore.__init__).parameters
        except (TypeError, ValueError):  # pragma: no cover
            _PYTREE_PARTIAL = False
    return _PYTREE_PARTIAL


def abstract_like(tree, sharding=None):
    """Abstract template for :func:`restore_checkpoint`: shapes/dtypes of
    ``tree`` with each leaf's target sharding.

    ``sharding`` may be None (restore to the leaves' current shardings —
    the resume-in-place case), a single ``jax.sharding.Sharding`` applied
    to every leaf, or a pytree of shardings matching ``tree``.
    """
    if sharding is None or isinstance(sharding, jax.sharding.Sharding):
        def leaf(x):
            s = sharding
            if s is None:
                s = x.sharding if isinstance(x, jax.Array) else None
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s)
        return jax.tree_util.tree_map(leaf, tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s),
        tree, sharding)


def save_checkpoint(path, state, force=True):
    """Write ``state`` (any pytree of arrays — the apex recipe bundles
    {params, opt_state, amp}) to ``path``. Sharded arrays are written
    shard-wise by their owning hosts; blocks until the checkpoint is
    committed."""
    _require_orbax()
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(os.fspath(path)), state, force=force)


def restore_checkpoint(path, template):
    """Restore the pytree at ``path``. ``template`` is either a concrete
    state (restore onto each leaf's current sharding) or the result of
    :func:`abstract_like` (restore onto explicit target shardings)."""
    _require_orbax()
    if any(isinstance(x, jax.Array)
           for x in jax.tree_util.tree_leaves(template)):
        template = abstract_like(template)
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(os.path.abspath(os.fspath(path)), template)


class CheckpointManager:
    """Retention + resume bookkeeping over :func:`save_checkpoint`.

    Mirrors the training-loop surface of the reference's save/resume
    snippets (examples/imagenet/main_amp.py:179-194 "resume from latest"):

        mgr = CheckpointManager(dir, max_to_keep=3)
        mgr.save(step, state)            # every save_interval steps
        step = mgr.latest_step()         # None if fresh start
        state = mgr.restore(step, state_template)
    """

    def __init__(self, directory, max_to_keep=5, save_interval_steps=1):
        _require_orbax()
        self._mgr = ocp.CheckpointManager(
            os.path.abspath(os.fspath(directory)),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                enable_async_checkpointing=False,
            ),
        )

    def save(self, step, state, force=False):
        """``force=True`` bypasses the ``save_interval_steps`` throttle
        (e.g. the final state of a run)."""
        return self._mgr.save(step, args=ocp.args.StandardSave(state),
                              force=force)

    def restore(self, step, template, partial=False):
        """``partial=True`` restores only the subtree named by
        ``template`` (e.g. params-only from a {params, opt, amp}
        checkpoint — the ``--no-load-optim`` case). Orbax pins one
        handler type per manager instance, so a partial restore must use
        a manager that has not saved in this process (a real resume
        naturally does)."""
        if any(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(template)):
            template = abstract_like(template)
        if partial:
            # PyTreeRestore ignores ShapeDtypeStruct shardings unless they
            # arrive as explicit restore_args (StandardRestore honors them
            # directly) — without this, arrays come back with the SAVED
            # topology's shardings, breaking cross-topology resume
            restore_args = ocp.checkpoint_utils.construct_restore_args(
                template)
            if _pytree_restore_supports_partial():
                return self._mgr.restore(
                    step, args=ocp.args.PyTreeRestore(
                        template, restore_args=restore_args,
                        partial_restore=True))
            # compat fallback (container orbax 0.7.x has no
            # partial_restore kwarg): restore the FULL saved tree —
            # the wanted top-level subtrees onto the template's
            # shardings, every other top-level subtree as plain host
            # numpy (no device placement to satisfy) — then post-filter
            # down to the template's keys
            saved = self._step_metadata(step)
            if saved is None:
                raise FileNotFoundError(
                    f"no readable checkpoint metadata for step {step}")
            item, rargs = dict(template), dict(restore_args)
            for key, sub in saved.items():
                if key in item:
                    continue
                item[key] = jax.tree_util.tree_map(lambda _: 0, sub)
                rargs[key] = jax.tree_util.tree_map(
                    lambda _: ocp.RestoreArgs(restore_type=np.ndarray),
                    sub)
            full = self._mgr.restore(
                step, args=ocp.args.PyTreeRestore(item,
                                                  restore_args=rargs))
            return {k: v for k, v in full.items() if k in template}
        return self._mgr.restore(
            step, args=ocp.args.StandardRestore(template))

    def latest_step(self):
        return self._mgr.latest_step()

    def _step_metadata(self, step):
        """The saved-pytree metadata tree for ``step`` (a nested dict of
        leaf metadata), or None when missing/unreadable. Orbax 0.7.x
        returns the tree directly from ``StandardCheckpointer.metadata``;
        newer releases wrap it in ``.item_metadata.tree``."""
        path = os.path.join(self._mgr.directory, str(step), "default")
        try:
            with ocp.StandardCheckpointer() as ckptr:
                md = ckptr.metadata(path)
            if isinstance(md, dict):
                return md
            return dict(md.item_metadata.tree)
        except Exception:
            return None

    def tree_keys(self, step):
        """Top-level keys of the pytree saved at ``step`` — lets a loader
        distinguish a params-only checkpoint (saved with no_save_optim)
        from a full {params, opt, amp} one before building the restore
        template. Returns None when the metadata is missing or unreadable
        (callers fall back to attempting the restore); assumes the
        default step layout (no ``step_prefix``/name formats, which this
        wrapper never sets)."""
        md = self._step_metadata(step)
        return sorted(md.keys()) if md is not None else None

    def all_steps(self):
        return list(self._mgr.all_steps())

    def close(self):
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# --------------------------------------------------------------------------
# Durability layer (ISSUE 6): crash-safe commits + resilient restore.
#
# The format is deliberately self-contained (raw leaf bytes + a JSON
# manifest, no orbax): an emergency restore after a wedged window must
# not depend on the optional stack, and the commit protocol must be
# auditable — `ckpt-<step>.bin` is written to a tmp name, fsynced and
# renamed; the manifest (carrying the data file's sha256) is written
# tmp + rename LAST, so the manifest rename is the commit point. A data
# file without a manifest is torn (a crash between the two renames) and
# is never restored; a data file whose bytes no longer hash to the
# manifest's sha256 (truncation, disk rot, an injected corruption
# fault) is never restored either — the restore walk falls back to the
# previous retained step.
# --------------------------------------------------------------------------

CKPT_FORMAT = "apex-ckpt-v1"
_HEADER_MAGIC = b"APEXCKPT1\n"


def _np_dtype(name):
    """Resolve a dtype name as recorded by ``str(arr.dtype)`` — numpy
    builtins directly, ml_dtypes extension types (bfloat16, fp8) via
    jnp so bf16 training state round-trips."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _host_leaves(state):
    """Flatten + device→host transfer (the scan-boundary fetch): every
    leaf as a C-contiguous numpy array. This is the only device
    interaction in a save — everything after it is host-side and can
    run on the background thread."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    host = []
    for x in leaves:
        a = np.asarray(jax.device_get(x))
        if not a.flags["C_CONTIGUOUS"]:
            # NB: ascontiguousarray, but only when needed — it promotes
            # 0-d arrays to shape (1,) and would corrupt scalar leaves
            a = np.ascontiguousarray(a)
        host.append(a)
    return host, str(treedef)


def _treedef_sha(treedef_str):
    return hashlib.sha1(treedef_str.encode()).hexdigest()[:16]


def _write_data_file(path, host_leaves):
    """Serialize leaves to *path*: magic + length-prefixed JSON header
    (shapes/dtypes) + concatenated raw bytes; fsynced before return.
    Returns the sha256 hexdigest, computed DURING the write — the
    GB-scale state must not pay a second full read just to hash."""
    header = json.dumps({
        "leaves": [{"shape": list(x.shape), "dtype": str(x.dtype)}
                   for x in host_leaves]}).encode()
    sha = hashlib.sha256()
    with open(path, "wb") as f:
        for chunk in (_HEADER_MAGIC, len(header).to_bytes(8, "little"),
                      header):
            f.write(chunk)
            sha.update(chunk)
        for x in host_leaves:
            b = x.tobytes()
            f.write(b)
            sha.update(b)
        f.flush()
        os.fsync(f.fileno())
    return sha.hexdigest()


def _parse_data_blob(blob):
    """(leaf_specs, payload_offset) out of an in-memory data blob —
    parsed only AFTER the caller's hash check passed."""
    if not blob.startswith(_HEADER_MAGIC):
        raise ValueError("bad checkpoint magic")
    n = int.from_bytes(blob[len(_HEADER_MAGIC):len(_HEADER_MAGIC) + 8],
                       "little")
    start = len(_HEADER_MAGIC) + 8
    header = json.loads(blob[start:start + n])
    return header["leaves"], start + n


def _sha256_file(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _data_path(directory, step):
    return os.path.join(directory, f"ckpt-{int(step):012d}.bin")


def _manifest_path(directory, step):
    return os.path.join(directory, f"ckpt-{int(step):012d}.json")


def manifest_id(manifest):
    """Content-hash id (``ck-`` + sha1 of the canonical manifest sans
    id): the provenance token a resumed run stamps into its ledger
    record, so a timing row's restore lineage is tamper-evident the
    same way ledger ids are."""
    body = json.dumps({k: v for k, v in manifest.items() if k != "id"},
                      sort_keys=True)
    return "ck-" + hashlib.sha1(body.encode()).hexdigest()[:10]


def durable_steps(directory):
    """Steps with a COMMITTED manifest, ascending. Data files without a
    manifest (a crash between the two renames) are invisible here — a
    torn checkpoint is never a restore candidate."""
    steps = []
    try:
        names = os.listdir(directory)
    except OSError:
        return steps
    for name in names:
        if name.startswith("ckpt-") and name.endswith(".json"):
            try:
                steps.append(int(name[5:-5]))
            except ValueError:
                continue
    return sorted(steps)


def read_durable_manifest(directory, step):
    """Parsed manifest for *step*, or None when missing/unparseable.
    Does NOT verify the data file — see :func:`restore_durable`."""
    try:
        with open(_manifest_path(directory, step)) as f:
            m = json.load(f)
        return m if isinstance(m, dict) else None
    except (OSError, ValueError):
        return None


def latest_durable_manifest(directory):
    """Manifest of the newest committed step (no data-file verification
    — a cheap on-disk peek for telemetry, e.g. the watchdog's
    ``bench_watchdog`` record)."""
    for step in reversed(durable_steps(directory)):
        m = read_durable_manifest(directory, step)
        if m is not None:
            return m
    return None


def _verify_manifest(directory, step, manifest):
    """The manifest-level durability invariants for one candidate step;
    returns a skip-reason string (falsy = consistent so far). Does not
    touch the data file's BYTES — the hash check happens against the
    blob the restore is about to read anyway (one read, not two)."""
    if manifest is None:
        return "unreadable manifest"
    if manifest.get("format") != CKPT_FORMAT:
        return f"unknown format {manifest.get('format')!r}"
    if manifest.get("step") != step:
        # a tampered/stale manifest claiming a different step than its
        # filename (the stale-step fault mode) must never restore as
        # this step — trajectory provenance would silently lie
        return (f"stale manifest (claims step {manifest.get('step')}, "
                f"file says {step})")
    if not os.path.exists(_data_path(directory, step)):
        return "data file missing"
    return None


def _verify_durable(directory, step, manifest):
    """Full durability verification for one candidate step INCLUDING
    the data-file hash (a separate read — use for on-disk audits;
    :func:`restore_durable` hashes the blob it reads instead)."""
    reason = _verify_manifest(directory, step, manifest)
    if reason:
        return reason
    if _sha256_file(_data_path(directory, step)) \
            != manifest.get("sha256"):
        return "content hash mismatch (torn/corrupt data file)"
    return None


def restore_durable(directory, template, step=None):
    """Restore the newest VALID durable checkpoint onto ``template``'s
    shardings; returns ``(state, manifest)`` or ``(None, None)``.

    The walk enforces the durability invariants: a torn data file (no
    manifest, or bytes that no longer match the manifest's sha256) is
    never restored; a stale manifest (step field disagreeing with the
    filename) is never restored; an incompatible tree (leaf count /
    treedef / shape / dtype vs ``template``) is skipped. Each rejection
    falls back to the previous retained step, so a crash mid-commit
    costs at most one checkpoint interval, never the run.

    ``step`` pins a single step (no fallback walk) — the explicit
    request contract: pinned and invalid raises instead of silently
    restoring something else.
    """
    import sys

    tleaves, ttreedef = jax.tree_util.tree_flatten(template)
    want_sha = _treedef_sha(str(ttreedef))
    pinned = step is not None
    candidates = [step] if pinned else list(reversed(
        durable_steps(directory)))
    for s in candidates:
        manifest = read_durable_manifest(directory, s)
        reason = _verify_manifest(directory, s, manifest)
        if not reason:
            if manifest.get("treedef_sha") != want_sha \
                    or manifest.get("n_leaves") != len(tleaves):
                reason = "state tree does not match the restore template"
        if not reason:
            try:
                with open(_data_path(directory, s), "rb") as f:
                    blob = f.read()
            except OSError as e:
                reason = f"unreadable data file ({e})"
        if not reason:
            # hash the blob just read (one pass over the bytes, not a
            # second file read) BEFORE parsing anything out of it:
            # torn/corrupt data is never restored, and the verdict
            # names the real failure (a corrupted header is a hash
            # mismatch, not a parse error)
            if hashlib.sha256(blob).hexdigest() \
                    != manifest.get("sha256"):
                reason = ("content hash mismatch (torn/corrupt "
                          "data file)")
        if not reason:
            try:
                specs, off = _parse_data_blob(blob)
            except (ValueError, KeyError) as e:  # hash-valid but
                # unparseable = a format bug, not corruption; still
                # fall back rather than crash the resume
                reason = f"unreadable data file ({e})"
        if not reason:
            leaves = []
            for spec, tmpl in zip(specs, tleaves):
                dtype = _np_dtype(spec["dtype"])
                shape = tuple(spec["shape"])
                if (np.shape(tmpl) != shape
                        or np.dtype(getattr(tmpl, "dtype", None))
                        != dtype):
                    reason = (f"leaf shape/dtype drift ({shape} "
                              f"{dtype} vs template)")
                    break
                count = int(np.prod(shape, dtype=np.int64))
                arr = np.frombuffer(blob, dtype=dtype, count=count,
                                    offset=off).reshape(shape)
                off += count * dtype.itemsize
                sharding = getattr(tmpl, "sharding", None)
                # place onto the template's sharding only when the
                # template leaf is explicitly placed (a mesh sharding
                # or a committed device_put) — an UNCOMMITTED template
                # leaf must restore uncommitted too, or a later jit
                # mixing it with mesh-sharded state sees conflicting
                # device pins
                if sharding is not None \
                        and getattr(tmpl, "_committed", True):
                    leaves.append(jax.device_put(arr, sharding))
                else:
                    leaves.append(jnp.asarray(arr))
            if not reason:
                return jax.tree_util.tree_unflatten(ttreedef,
                                                    leaves), manifest
        if pinned:
            raise ValueError(
                f"checkpoint step {s} in {directory}: {reason}")
        print(f"# checkpoint: skipping step {s} ({reason}) — "
              "falling back", file=sys.stderr, flush=True)
    return None, None


def resume_provenance(writer, template, expect_meta=None):
    """The ONE resume entry for the harnesses (profile_gpt): restore
    the newest valid checkpoint and build the provenance block
    check_bench_labels check 5 polices.

    Returns ``(restored_state, step0, resumed_from)`` —
    ``(None, 0, None)`` when no valid checkpoint exists or when
    ``expect_meta`` mismatches. ``expect_meta`` guards the config axes
    the state TREE cannot encode (e.g. the bench batch: params/opt/
    scaler shapes are batch-independent, so only the saved meta can
    refuse a cross-config resume). ``resumed_from`` is
    ``{ckpt, step, pins[, pin_drift]}`` with pins compared through
    ``ledger.measurement_pins`` — one implementation, so the producers
    can never drift from the checker."""
    import sys

    from apex_tpu.telemetry import ledger

    restored, manifest = writer.restore_latest(template)
    if restored is None:
        return None, 0, None
    meta = manifest.get("meta") or {}
    for key, want in (expect_meta or {}).items():
        got = meta.get(key)
        if got is not None and got != want:
            print(f"# checkpoint: refusing resume from "
                  f"{manifest.get('id')} — saved {key}={got!r} but this "
                  f"run has {key}={want!r} (cross-config resume); cold "
                  "start", file=sys.stderr, flush=True)
            return None, 0, None
    step0 = int(meta.get("step", manifest["step"]))
    # filtered at the source: a checkpoint written by a foreign/older
    # producer may carry infra knobs in its meta — they are not pins
    saved_pins = ledger.measurement_pins(meta.get("knob_pins") or {})
    resumed_from = {"ckpt": manifest.get("id"), "step": step0,
                    "pins": saved_pins}
    drift = ledger.pin_drift(saved_pins, ledger.knob_pins())
    if drift:
        # resumed under different measurement pins than the checkpoint
        # was trained with: the run proceeds (the state is still
        # valid) but the provenance names the drift and check 5
        # refuses citations
        resumed_from["pin_drift"] = drift
        print(f"# resume pin drift: {json.dumps(drift)}",
              file=sys.stderr, flush=True)
    print(f"# resumed from {manifest.get('id')} at step {step0}",
          file=sys.stderr, flush=True)
    return restored, step0, resumed_from


class DurableCheckpointer:
    """Crash-safe checkpoint writer with an optional background commit
    thread (the async-checkpointing half of PAPERS.md arXiv:2011.03641
    — host-side work off the step critical path).

    ``save(step, state, meta=...)`` fetches the state to host (the only
    device interaction) and either commits inline (sync mode — the
    DEFAULT, per the measured-dispatch rule: async flips only after the
    overhead A/B in PERF.md lands) or enqueues the commit on a bounded
    queue drained by one background thread (``APEX_CKPT_ASYNC=1``). A
    full queue BLOCKS the caller (backpressure): checkpoints are
    dropped never, delayed at most.

    Commit protocol: data tmp → fsync → rename; manifest (sha256 of the
    data file, treedef hash, caller meta, content-hash id) tmp → fsync
    → rename. The manifest rename is the commit point; every fault
    between the two renames leaves the PREVIOUS checkpoint as the
    newest valid one. Retention removes manifest-first, so a
    half-deleted old step degrades to an invisible torn file.

    ``snapshot()`` is the telemetry block stamped into bench's JSON
    line and ledger records: ``{saves, queue_depth, commit_ms,
    last_step}`` (+ ``async``/``errors``).
    """

    def __init__(self, directory, max_to_keep=None, async_save=None,
                 queue_size=None):
        self.directory = os.path.abspath(os.fspath(directory))
        os.makedirs(self.directory, exist_ok=True)
        self.max_to_keep = max(1, int(
            os.environ.get("APEX_CKPT_KEEP", "2")
            if max_to_keep is None else max_to_keep))
        self.async_save = (os.environ.get("APEX_CKPT_ASYNC") == "1"
                           if async_save is None else bool(async_save))
        qsize = int(os.environ.get("APEX_CKPT_QUEUE", "2")
                    if queue_size is None else queue_size)
        self._q = queue.Queue(maxsize=max(1, qsize))
        self._thread = None
        # RLock, not Lock: the emergency SIGTERM handler runs
        # commit_now() ON the main thread, possibly interrupting a
        # frame that already holds this lock — a non-reentrant lock
        # would deadlock the handler inside its grace window
        self._lock = threading.RLock()
        self._stats = {"saves": 0, "commit_ms": None, "last_step": None,
                       "errors": 0, "last_error": None}

    # ------------------------------------------------------------- save
    def save(self, step, state, meta=None):
        """Checkpoint ``state`` (any pytree) as ``step``. ``meta`` must
        be JSON-serializable — the resume surface rides here (knob
        pins, RNG seed bookkeeping, provenance)."""
        host, treedef_str = _host_leaves(state)
        if self.async_save:
            self._ensure_thread()
            # bounded queue: a serializer that cannot keep up BLOCKS
            # the training loop here (backpressure) instead of growing
            # host memory without bound or dropping checkpoints
            self._q.put((int(step), host, treedef_str, dict(meta or {})))
            return None
        return self._commit(int(step), host, treedef_str,
                            dict(meta or {}))

    def commit_now(self, step, state, meta=None):
        """Synchronous commit that BYPASSES the async queue — the
        emergency-save path: a signal handler must not block on the
        queue's non-reentrant internals (``Queue.put``/``join``) that
        its own interrupted frame may hold. ``state`` may already be a
        host pytree (the staged emergency copy)."""
        host, treedef_str = _host_leaves(state)
        return self._commit(int(step), host, treedef_str,
                            dict(meta or {}))

    def _ensure_thread(self):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._worker, name="apex-ckpt-writer",
                    daemon=True)
                self._thread.start()

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            try:
                self._commit(*item)
            except BaseException as e:  # a failed commit must never
                # kill the writer thread — the NEXT save still commits,
                # and the failure is visible in the telemetry block
                with self._lock:
                    self._stats["errors"] += 1
                    self._stats["last_error"] = \
                        f"{type(e).__name__}: {str(e)[:200]}"
            finally:
                self._q.task_done()

    def _commit(self, step, host_leaves, treedef_str, meta):
        from apex_tpu.resilience import faults

        # the whole commit runs under the writer lock: the emergency
        # SIGTERM handler's commit_now (main thread) must not
        # interleave file writes with the background worker committing
        # the same step — the lock is an RLock, so a handler that
        # interrupted a main-thread commit re-enters instead of
        # deadlocking, and a worker mid-commit just finishes first
        # (bounded by one commit). The per-writer tmp suffix is belt
        # and suspenders for any OTHER process sharing the directory.
        with self._lock:
            return self._commit_locked(step, host_leaves, treedef_str,
                                       meta, faults)

    def _commit_locked(self, step, host_leaves, treedef_str, meta,
                       faults):
        t0 = time.perf_counter()
        data = _data_path(self.directory, step)
        tmp = (f"{data}.tmp.{os.getpid()}."
               f"{threading.get_ident()}")
        sha = _write_data_file(tmp, host_leaves)
        # slow-disk / crash-before-visibility fault site: everything up
        # to here left no visible artifact but the tmp file
        faults.fire("ckpt_commit", step=step, phase="serialized")
        os.replace(tmp, data)
        # the torn window: data visible, manifest not yet committed — a
        # SIGKILL here must leave the PRIOR checkpoint as the newest
        # valid one (the restore walk ignores manifest-less data)
        faults.fire("ckpt_commit", step=step, phase="data_visible")
        manifest = {
            "format": CKPT_FORMAT,
            "step": step,
            "ts": round(time.time(), 3),
            "sha256": sha,
            "bytes": os.path.getsize(data),
            "n_leaves": len(host_leaves),
            "treedef_sha": _treedef_sha(treedef_str),
            "meta": meta,
        }
        # stale-step tamper site (test-only): a fault plan can rewrite
        # manifest fields so the restore walk's step-consistency check
        # is exercised against a real commit
        manifest = faults.transform_json("ckpt_manifest", manifest,
                                         step=step)
        manifest["id"] = manifest_id(manifest)
        mpath = _manifest_path(self.directory, step)
        mtmp = f"{mpath}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(mtmp, "w") as f:
            json.dump(manifest, f, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(mtmp, mpath)  # the commit point
        # post-commit disk-rot site (test-only): damage the committed
        # data file so the hash-check fallback is exercised
        faults.damage_file("ckpt_data", data, step=step)
        self._retain()
        dt_ms = round((time.perf_counter() - t0) * 1e3, 2)
        with self._lock:
            self._stats["saves"] += 1
            self._stats["commit_ms"] = dt_ms
            if self._stats["last_step"] is None \
                    or step >= self._stats["last_step"]:
                self._stats["last_step"] = step
        return manifest

    def _retain(self):
        steps = durable_steps(self.directory)
        for step in steps[:-self.max_to_keep or None]:
            # manifest FIRST: if the delete is interrupted the step
            # degrades to a torn (invisible) data file, never to a
            # manifest pointing at missing data
            for path in (_manifest_path(self.directory, step),
                         _data_path(self.directory, step)):
                try:
                    os.remove(path)
                except OSError:
                    pass

    # ------------------------------------------------------- lifecycle
    def flush(self):
        """Drain every queued commit (no-op in sync mode). The
        emergency-save path calls this so a SIGTERM'd run's final
        checkpoint is COMMITTED, not parked on a dying queue."""
        if self._thread is not None and self._thread.is_alive():
            self._q.join()

    def close(self):
        self.flush()
        with self._lock:
            t = self._thread
            self._thread = None
        if t is not None and t.is_alive():
            self._q.put(None)
            t.join(timeout=60)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------- telemetry
    def snapshot(self):
        with self._lock:
            snap = dict(self._stats)
        snap["queue_depth"] = self._q.qsize()
        snap["async"] = self.async_save
        return snap

    # --------------------------------------------------------- restore
    def latest_step(self):
        steps = durable_steps(self.directory)
        return steps[-1] if steps else None

    def all_steps(self):
        return durable_steps(self.directory)

    def restore_latest(self, template):
        """(state, manifest) of the newest VALID checkpoint (walking
        past torn/corrupt/stale ones), or (None, None)."""
        return restore_durable(self.directory, template)

    def restore(self, step, template):
        """Pinned-step restore: raises on an invalid checkpoint instead
        of silently restoring a different step (explicit request ≠
        preference)."""
        return restore_durable(self.directory, template, step=step)
