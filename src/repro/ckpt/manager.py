"""CheckpointManager — training state as layered, content-addressed images.

A training checkpoint is an *image* whose layers mirror a Dockerfile:

    FROM <arch>                      (config layer, empty)
    COPY params/embed                (content layer)
    COPY params/blocks               (content layer — the big one)
    COPY params/head                 (content layer)
    RUN  adamw_init                  (content layer: m/v/master, derives
                                      from the params layers)
    ENV  step=<n>                    (config layer)

Two save modes, benchmarked against each other (the paper's comparison):

* ``save_full``  — Docker-faithful baseline: `build_image` with DLC cache
  rules; any param change re-serializes + re-hashes whole layers and falls
  through to everything below.
* ``save_incremental`` — the paper's code-injection method: per-chunk diff
  (optionally pre-filtered by on-device fingerprints), clone-before-inject,
  chunk-level writes, checksum re-key. Cost O(changed bytes), not O(state).

The fingerprint-mode save is a fused device+host pipeline (the repo's perf
tentpole; benchmarks/run.py::bench_incremental_save records it):

  1. device   — ``fingerprint_tree_packed``: every leaf's uint32 lanes are
     packed into ONE buffer and fingerprinted in a single dispatch
     (``packed_fingerprints=False`` keeps the per-leaf dispatch baseline);
     only the (total_chunks, 2) table crosses D2H (``BuildReport.bytes_d2h``).
  2. diff     — fingerprint compare prefilters unchanged chunks
     (``BuildReport.chunks_prefiltered``); only changed chunk *ranges* are
     SHA-256'd on the shared hash pool, as zero-copy slices of the leaf's
     byte view (``chunker.tensor_byte_view``). Leaves stay device-resident
     until a range is actually touched.
  3. store    — all changed layers go through ONE multi-layer injection
     (``core.inject.inject_image_multi``): clone-before-inject per layer,
     a single downstream re-key walk and a single manifest commit per
     save, with per-chunk fsyncs deferred to that commit point and issued
     as one concurrent batch. ``BuildReport.per_layer`` attributes
     chunks/bytes/re-keys to each layer of the checkpoint image.

Async: serialization of the *diff payload* happens on the caller thread
(cheap: only changed chunks), blob/manifest writes go to a background
executor; `wait()` joins. Atomicity: the image manifest rename is the
commit point (see core.store), so a crash mid-save leaves the previous
checkpoint intact — tests/test_ft.py kills a save mid-flight to prove it.
"""
from __future__ import annotations

import re
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from ..core import (BuildReport, Instruction, LayerStore, PassiveRegistry,
                    RelayNode, StructureChangeError, diff_image,
                    fingerprint_tree, fingerprint_tree_packed,
                    inject_image_multi, push_delta, replicate_fanout)
from ..ft.faults import CrashInjected


def flatten_tree(tree, prefix="") -> Dict[str, np.ndarray]:
    """pytree -> flat {path: array} with '/'-joined keys.

    Leaves are kept AS-IS (device arrays stay on device): forcing
    ``np.asarray`` here would pull the entire checkpoint over the host link
    on every save — exactly the O(state) transfer the fingerprint prefilter
    exists to avoid. The diff's byte view (chunker.tensor_byte_view)
    converts lazily, and with fingerprints enabled only the *changed*
    tensors' bytes ever cross D2H.
    """
    out: Dict[str, np.ndarray] = {}

    def walk(t, path):
        if isinstance(t, dict):
            for k2 in sorted(t.keys()):
                walk(t[k2], f"{path}/{k2}" if path else k2)
        elif hasattr(t, "dtype") and hasattr(t, "shape"):
            out[path] = t
        else:
            out[path] = np.asarray(t)

    walk(tree, prefix)
    return out


def unflatten_tree(flat: Dict[str, np.ndarray]) -> Any:
    root: Dict[str, Any] = {}
    for path, v in flat.items():
        parts = path.split("/")
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return root


# Step-tag helpers, shared by CheckpointManager and serve.CheckpointFollower
# so the trainer and a serving replica can never disagree about the tag
# format or the retention semantics.

_STEP_TAG = re.compile(r"step-(\d+)")


def step_of_tag(tag: str) -> Optional[int]:
    """The step number of a canonical step tag, None for anything else.
    User-pushed tags (``best``, ``release``, even ``step-final``) are not
    step tags: they must never crash step parsing and never participate in
    retention — skipping them here is what keeps ``latest_step`` and
    ``prune_steps`` safe in an image with mixed tags. Canonical means the
    tag round-trips through ``CheckpointManager.tag_of`` — every caller of
    ``latest_step`` reconstructs the tag as ``step-{n:08d}``, so a
    hand-pushed ``step-9`` must count as a user tag too (it would
    reconstruct to a tag that doesn't exist)."""
    m = _STEP_TAG.fullmatch(tag)
    if not m:
        return None
    n = int(m.group(1))
    return n if tag == f"step-{n:08d}" else None


def latest_step(store: LayerStore, image: str,
                fresh: bool = False) -> Optional[int]:
    """Newest step number among an image's ``step-<digits>`` tags; tags
    that aren't step tags are skipped, not parsed. ``fresh`` bypasses the
    store's tag cache (needed when another process commits the tags)."""
    return max((s for s in (step_of_tag(t)
                            for t in store.list_tags(image, fresh=fresh))
                if s is not None), default=None)


def prune_steps(store: LayerStore, image: str, keep: int) -> bool:
    """Retention + reclamation: drop step tags beyond the ``keep`` newest,
    then mark-and-sweep the store so their exclusive blobs/layers are
    actually deleted (unbounded disk growth otherwise). Returns whether
    anything was removed. ``keep<=0`` keeps everything.

    Ordering is NUMERIC on the parsed step, and non-canonical tags
    (``best``, ``release``, ``step-final``, a hand-pushed ``step-9``) are
    never candidates — retention must not be able to delete a user's
    pin, and must never mistake one for the newest checkpoint.

    Tags under an active retention LEASE (a relay pinning the base a
    lagging child's delta still negotiates against — see
    ``LayerStore.acquire_lease``) are skipped, not deleted: retention on
    a relay must never pull the base out from under an in-flight child
    pull. The skip is tag-granular and temporary — once the child commits
    (release) or dies (TTL expiry), the next prune cycle reclaims it."""
    if keep <= 0:
        return False
    steps = sorted((s, t) for t in store.list_tags(image)
                   if (s := step_of_tag(t)) is not None)
    removed = False
    for _, t in steps[:-keep]:
        # remove_image refuses leased tags on its own; checking here too
        # keeps the gc() decision honest (a fully-leased prune is a no-op)
        if store.leased(image, t):
            continue
        removed = store.remove_image(image, t) or removed
    if removed:
        store.gc()
    return removed


@dataclass
class CheckpointPolicy:
    every_steps: int = 100
    keep: int = 3
    incremental: bool = True          # the paper's technique (vs baseline)
    use_fingerprints: bool = False    # on-device change detection
    packed_fingerprints: bool = True  # ONE dispatch for the whole tree
                                      # (False = per-leaf dispatch baseline)
    async_write: bool = True
    chunk_bytes: int = 1 << 20
    durability: str = "batch"         # the store-wide default: per-chunk
                                      # fsyncs defer to one concurrent
                                      # flush at the manifest commit point
                                      # ("full" = seed per-write fsyncs)
    # passive-registry publish-on-save policy (active only when the
    # manager is given a ``registry=``): after each save, advertise a
    # full head bundle plus one squashed bundle per span, where span k
    # reaches back k COMMITTED step tags (not k raw steps — saves land
    # every ``every_steps`` and retention prunes, so committed tags are
    # the only honest distance metric). (1, 4, 8) keeps a fresh edge one
    # tiny hop from head while an edge that slept through 8 saves still
    # finds a single squashed bundle instead of a full pull.
    publish_spans: Tuple[int, ...] = (1, 4, 8)


class CheckpointManager:
    """See module docstring. Multi-tenant form: ``image=`` names this
    manager's image (default ``"ckpt"``), and several managers may share
    ONE ``LayerStore`` (pass ``store=``; ``root`` is then ignored) — the
    cross-image blob universe, where tenant checkpoints dedup against each
    other and against a shared base. ``base_image=("name", "tag")`` forks
    this manager's FIRST save from another image in the same store: the
    build runs with that image as its DLC cache parent, so unchanged
    layers reuse the base's layer ids outright — which is exactly what
    lets ``replicate``/``replicate_fanout`` later ship only the adapter
    delta to replicas that already hold the base image. Retention
    (``prune_steps`` + the store-wide ``gc()``) is per image but
    cross-image safe: pruning one tenant never sweeps blobs a sibling
    image still reaches."""

    IMAGE = "ckpt"

    def __init__(self, root: str, arch: str,
                 policy: Optional[CheckpointPolicy] = None,
                 image: Optional[str] = None,
                 base_image: Optional[Tuple[str, str]] = None,
                 store: Optional[LayerStore] = None,
                 registry=None):
        self.policy = policy or CheckpointPolicy()
        # a shared store keeps ITS chunking/durability: tenants of one
        # universe must agree on chunk geometry or dedup silently dies
        self.store = store if store is not None else LayerStore(
            root, chunk_bytes=self.policy.chunk_bytes,
            durability=self.policy.durability)
        self.image = image or self.IMAGE
        self.base_image = base_image
        self.arch = arch
        # passive bundle registry to publish into after each save (a
        # PassiveRegistry, or a local directory path). Publishing is
        # best-effort: see _publish.
        self.registry = registry if registry is None or \
            isinstance(registry, PassiveRegistry) \
            else PassiveRegistry(str(registry))
        if self.registry is not None:
            self.registry.attach_gc(self.store, self.image)
        self.last_publish = None
        self.last_publish_error: Optional[str] = None
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._pending: Optional[Future] = None
        self._pending_step: Optional[int] = None
        self._last_fps: Dict[str, np.ndarray] = {}
        self.last_report: Optional[BuildReport] = None

    # ------------------------------------------------------------ layout
    def _instructions(self) -> List[Instruction]:
        return [
            Instruction("FROM", self.arch, "config"),
            Instruction("COPY", "params/embed", "content"),
            Instruction("COPY", "params/blocks", "content"),
            Instruction("COPY", "params/head", "content"),
            Instruction("RUN", "opt_state", "content",
                        derives_from=[]),   # values evolve, not re-derived
            Instruction("ENV", "meta", "config"),
        ]

    def _payloads(self, params, opt_state, step: int
                  ) -> Dict[str, Dict[str, np.ndarray]]:
        flat = flatten_tree(params, "params")
        embed = {k: v for k, v in flat.items()
                 if k.startswith("params/embed")}
        blocks = {k: v for k, v in flat.items()
                  if k.startswith("params/blocks")}
        head = {k: v for k, v in flat.items()
                if not k.startswith(("params/embed", "params/blocks"))}
        opt = flatten_tree(opt_state, "opt")
        opt["opt/__step__"] = np.asarray([step], np.int32)
        return {"params/embed": embed, "params/blocks": blocks,
                "params/head": head, "opt_state": opt}

    # -------------------------------------------------------------- save
    def tag_of(self, step: int) -> str:
        return f"step-{step:08d}"

    def latest_step(self) -> Optional[int]:
        # list_tags is cached in the store (invalidated at the manifest
        # commit / image removal), so polling this every save is free.
        return latest_step(self.store, self.image)

    def wait(self) -> Optional[BuildReport]:
        if self._pending is not None:
            with obs.span("ckpt.wait", step=self._pending_step):
                self.last_report = self._pending.result()
            self._pending = None
        return self.last_report

    def save(self, step: int, params, opt_state) -> BuildReport:
        """Dispatches to full or incremental save per policy."""
        with obs.span("ckpt.save", step=step) as call:
            self.wait()
            payloads = self._payloads(params, opt_state, step)
            if self.policy.incremental and self.latest_step() is not None:
                fn = self._save_incremental
            else:
                fn = self._save_full
            if self.policy.async_write:
                self._pending = self._pool.submit(self._write, call.id, fn,
                                                  step, payloads)
                self._pending_step = step
                return BuildReport()    # async: report available at wait()
            report = self._write(call.id, fn, step, payloads)
        self.last_report = report
        return report

    @staticmethod
    def _write(parent: int, fn, step: int, payloads) -> BuildReport:
        """The save's write, on the writer thread when it is async."""
        with obs.span("ckpt.write", parent=parent, step=step):
            return fn(step, payloads)

    def _compute_fps(self, payloads: Dict[str, Dict[str, np.ndarray]],
                     stats: dict) -> Dict[str, np.ndarray]:
        """Fingerprint every tensor of the checkpoint. Packed mode issues
        ONE fused device dispatch + one D2H transfer for the whole tree
        (core.fingerprint.fingerprint_tree_packed); per-leaf mode is the
        dispatch-per-tensor baseline kept for benchmarking."""
        union: Dict[str, np.ndarray] = {}
        for tree in payloads.values():
            union.update(tree)
        if self.policy.packed_fingerprints:
            return fingerprint_tree_packed(union, self.policy.chunk_bytes,
                                           stats=stats)
        fps = fingerprint_tree(union, self.policy.chunk_bytes)
        stats["bytes_d2h"] = stats.get("bytes_d2h", 0) + \
            sum(v.nbytes for v in fps.values())
        stats["device_dispatches"] = stats.get("device_dispatches", 0) + \
            len(fps)
        return fps

    def _save_full(self, step: int,
                   payloads: Dict[str, Dict[str, np.ndarray]],
                   fps: Optional[Dict[str, np.ndarray]] = None
                   ) -> BuildReport:
        prev = self.latest_step()
        parent = (self.image, self.tag_of(prev)) if prev is not None \
            else self.base_image
        providers = {k: (lambda p=v: p) for k, v in payloads.items()}
        ins = self._instructions()
        ins[-1] = Instruction("ENV", f"meta step={step}", "config")
        _, _, report = self.store.build_image(
            self.image, self.tag_of(step), ins, providers, parent=parent,
            arch=self.arch)
        if self.policy.use_fingerprints:
            # bootstrap the change detector for the NEXT incremental save
            stats: dict = {}
            self._last_fps = fps if fps is not None else \
                self._compute_fps(payloads, stats)
            report.bytes_d2h += stats.get("bytes_d2h", 0)
        self._gc()
        self._publish()
        return report

    def _save_incremental(self, step: int,
                          payloads: Dict[str, Dict[str, np.ndarray]]
                          ) -> BuildReport:
        """The paper's injection path (C1-C4) as ONE multi-layer batch: a
        save touching embed+blocks+head pays a single clone+re-key walk and
        a single manifest commit (durability="batch" defers every blob
        fsync of the batch to that commit point), with per-layer cost
        attribution in ``BuildReport.per_layer``."""
        prev = self.latest_step()
        stats: dict = {}
        new_fps: Dict[str, np.ndarray] = {}
        with obs.span("ckpt.diff") as span:
            manifest, _ = self.store.read_image(self.image,
                                                self.tag_of(prev))
            if self.policy.use_fingerprints:
                new_fps = self._compute_fps(payloads, stats)
            layers = [self.store.read_layer(lid)
                      for lid in manifest.layer_ids]
            counts: dict = {}
            if self.policy.use_fingerprints:
                diffs = diff_image(layers, payloads, old_fps=self._last_fps,
                                   new_fps=new_fps, stats=counts)
            else:
                diffs = diff_image(layers, payloads, stats=counts)
            span.count(**counts)
        try:
            # one batched transaction under the POLICY's durability mode
            # (batch = one deferred fsync flush at the manifest commit)
            _, _, report = inject_image_multi(
                self.store, self.image, self.tag_of(prev),
                self.tag_of(step), diffs,
                providers={k: (lambda p=v: p) for k, v in payloads.items()},
                durability=self.policy.durability)
        except StructureChangeError:
            # structure changed ("compiled" case) -> rebuild fall-back;
            # any other failure of the injection path is a failed save
            report = self._save_full(step, payloads,
                                     fps=new_fps if new_fps else None)
        report.bytes_d2h += stats.get("bytes_d2h", 0)
        if self.policy.use_fingerprints:
            self._last_fps = new_fps or self._last_fps
        self._gc()
        self._publish()
        return report

    def _gc(self) -> None:
        """Retention (see ``prune_steps``). Runs post-commit on the save
        thread, so no batch transaction is open; LayerStore.gc additionally
        refuses to sweep anything still dirty in an open one."""
        with obs.span("ckpt.retention"):
            prune_steps(self.store, self.image, self.policy.keep)

    def _publish(self) -> None:
        """Advertise the just-committed head in the passive bundle
        registry (``policy.publish_spans``): a full bundle plus one
        squashed bundle per span back over the committed step tags.
        Best-effort by contract — a dead object store must never fail a
        save, so every error is swallowed into ``last_publish_error``
        and the next save's publish retries (the index stays
        stale-but-consistent in the meantime, which followers already
        treat as a fall-back signal)."""
        if self.registry is None:
            return
        with obs.span("ckpt.publish"):
            try:
                steps = sorted(s for t in self.store.list_tags(self.image)
                               if (s := step_of_tag(t)) is not None)
                if not steps:
                    return
                froms = [self.tag_of(steps[-1 - span])
                         for span in self.policy.publish_spans
                         if span < len(steps)]
                self.last_publish = self.registry.publish_image(
                    self.store, self.image, self.tag_of(steps[-1]),
                    from_tags=froms)
                self.last_publish_error = None
            except CrashInjected:
                raise           # the saver process dying is not "a dead
                # object store" — best-effort must not swallow the crash
            except Exception as e:  # noqa: BLE001
                self.last_publish_error = f"{type(e).__name__}: {e}"

    # --------------------------------------------------------- replication
    def replicate(self, remote=None, step: Optional[int] = None,
                  relay=None, source: Optional[str] = None):
        """Ship a checkpoint to serving/registry stores as a DELTA: one
        have-set negotiation + only the chunks a remote is missing cross
        the wire. After an incremental save this is O(changed bytes) —
        call it at the save cadence to keep serving replicas hot.

        ``remote`` is a LayerStore or filesystem path (-> ``push_delta``,
        returns PushStats, failures raise), or a list/tuple of them (->
        ``replicate_fanout``, returns FanoutStats: ONE negotiation round +
        one source read pass for the whole fleet, per-replica failures
        isolated so one sick replica never blocks the rest).

        ``relay`` adds multi-hop tiers (trainer -> M relays -> N edge
        followers each): a dict ``{relay_store_or_path: [children...]}``,
        or a sequence of ``RelayNode``s / ``(store_or_path, children)``
        pairs; children may themselves be any of those shapes, so tiers
        nest. Relays and plain remotes ride the SAME fan-out (one
        negotiation round, one source read pass); each relay re-fans its
        pull to its children — streaming from the in-flight pull with
        ``source="inflight"``, after its own commit with "commit", or each
        node's configured mode when None. Returns FanoutStats whose
        ``replicas[i].children`` nests each relay's downstream outcome."""
        self.wait()
        if remote is None and relay is None:
            raise ValueError("replicate() needs a destination: pass "
                             "remote=, relay=, or both")
        step = step if step is not None else self.latest_step()
        if step is None:
            return None

        def as_store(r):
            # RelayNodes pass through untouched (replicate_fanout accepts
            # receivers directly), so a relay may ride in a remote list
            if isinstance(r, (LayerStore, RelayNode)):
                return r
            return LayerStore(str(r), chunk_bytes=self.policy.chunk_bytes)

        def as_relays(spec):
            # dict {store: children} | sequence of RelayNode /
            # (store, children) pairs — children recurse through the same
            # shapes, so tiers nest in any of them
            out = []
            for item in (spec.items() if isinstance(spec, dict) else spec):
                if isinstance(item, RelayNode):
                    out.append(item)
                    continue
                store, children = item
                if isinstance(children, (str, bytes)):
                    # would be iterated per CHARACTER into junk stores
                    raise TypeError("relay children must be a sequence, "
                                    f"not a bare path: {children!r}")
                kids = []
                for c in children:
                    if isinstance(c, dict):
                        kids.extend(as_relays(c))
                    elif isinstance(c, (tuple, RelayNode)):
                        kids.extend(as_relays([c]))
                    else:
                        kids.append(as_store(c))
                out.append(RelayNode(as_store(store), children=kids))
            return out

        if relay is not None:
            relays = as_relays(relay)
            plain = [] if remote is None else (
                list(remote) if isinstance(remote, (list, tuple)) else [remote])
            return replicate_fanout(
                self.store, [as_store(r) for r in plain] + relays,
                self.image, self.tag_of(step), source=source)
        if isinstance(remote, (list, tuple)):
            # source re-modes RelayNodes the caller put in the list; with
            # none present it would be a silent no-op, so reject it the
            # same way the single-remote branch does
            if source is not None and \
                    not any(isinstance(r, RelayNode) for r in remote):
                raise ValueError("source= only applies to relay "
                                 "topologies; no relay in the remote list")
            return replicate_fanout(self.store, [as_store(r) for r in remote],
                                    self.image, self.tag_of(step),
                                    source=source)
        if source is not None and not isinstance(remote, RelayNode):
            raise ValueError("source= only applies to relay topologies; a "
                             "plain remote has no re-fan to mode")
        if isinstance(remote, RelayNode):
            fan = replicate_fanout(self.store, [remote], self.image,
                                   self.tag_of(step), source=source)
            rep = fan.replicas[0]
            if rep.exception is not None:
                raise rep.exception
            return fan
        return push_delta(self.store, as_store(remote), self.image,
                          self.tag_of(step))

    # ------------------------------------------------------------ restore
    def restore(self, step: Optional[int] = None
                ) -> Optional[Tuple[Any, Any, int]]:
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        flat = self.store.load_image_payload(self.image, self.tag_of(step))
        opt_flat = {k[len("opt/"):]: v for k, v in flat.items()
                    if k.startswith("opt/")}
        saved_step = int(opt_flat.pop("__step__")[0])
        params_flat = {k[len("params/"):]: v for k, v in flat.items()
                       if k.startswith("params/")}
        return (unflatten_tree(params_flat), unflatten_tree(opt_flat),
                saved_step)
