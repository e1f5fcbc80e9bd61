"""KVStoreMesh — collectives-backed synchronous data parallelism behind
the kvstore facade (``kv.create("mesh")``; ROADMAP item 1, SURVEY §5.8).

The ``dist_sync`` store already runs its gradient sum as an in-program
cross-process psum, but one compiled program PER KEY, dispatched push by
push; ``dist_async`` is a host round-trip per key by design. This
backend is the TPU-native end state of that progression:

* **Bucketed exchange** — pushed gradients only STASH; keys pack into
  flat per-dtype buckets (``dist.bucket_bytes`` autotune knob /
  ``MXNET_DIST_BUCKET_BYTES``) and each bucket's collective dispatches
  the moment its keys are all present. jax dispatch is asynchronous, so
  the first buckets' all-reduce overlaps the device still executing the
  rest of backward and the host still walking later keys — the
  reference's multi-machine overlap trick (gradient bucketing in
  kvstore_dist.h) compiled into the step.
* **Zero host RPCs on the step path** — there is no parameter server
  and no socket: the exchange is ``jax.jit``-compiled collectives over a
  one-device-per-process mesh (ICI/DCN on TPU pods, gloo on the CPU
  fake cluster). The waterfall's ``kvstore`` segment collapses to the
  host-side dispatch sliver (rows are stamped ``collective``).
* **ZeRO-1 optimizer sharding** (``MXNET_MESH_ZERO1``, default on) —
  plain all-reduce is replaced by reduce-scatter + all-gather: each
  rank receives only its 1/N contiguous shard of the summed gradient,
  runs the optimizer update (and owns the optimizer state) for that
  shard alone, then the updated parameter shards all-gather back to
  every rank. Optimizer-state memory per chip drops ~1/N. Elementwise
  optimizers (SGD/momentum/Adam family) make the sharded update
  bit-identical to the unsharded one; the per-element gradient sum is
  the same ``sum(axis=0)`` program either way, so mesh-vs-zero1 parity
  is exact, and parity vs a single-device fit of the same global batch
  is exact up to fp32 reassociation of the per-rank partial sums
  (documented tolerance, tests/test_mesh_kvstore.py).

Rank identity rides the jax process index: construction stamps
``dist_trace.set_rank`` so the fleet timeline, /statusz dist section and
``tools/dist_report.py`` work without any kvstore server, and — when
``MXNET_DIST_SENTINEL`` is armed — per-step fingerprints meet on every
rank via one small ``process_allgather`` instead of an RPC to shard 0.
"""
from __future__ import annotations

import os
import pickle

from .base import MXNetError
from .kvstore import KVStore, _ctype_key_value, _ensure_distributed, \
    _updater_key
from .ndarray.ndarray import _from_data

__all__ = ["KVStoreMesh"]

_SENTINEL_FIELDS = ("rank", "step", "grad_norm", "param_norm", "loss")


class KVStoreMesh(KVStore):
    """Synchronous data-parallel store whose exchange is in-program
    collectives (see module docstring). ``push`` stashes, ``pull``
    settles; between the two, whole buckets fly as single compiled
    reduce-scatter/all-reduce programs."""

    # model._update_params_on_kvstore pushes ALL keys before pulling any
    # when this is set, so bucket dispatch can overlap backward
    bucketed = True

    def __init__(self, zero1=None, bucket_bytes=None):
        if os.environ.get("MXTPU_COORDINATOR"):
            # fake-cluster / launcher path; a user-initialized
            # jax.distributed (real pods) is detected inside the guard
            _ensure_distributed()
        super().__init__("mesh")
        # collective semantics for barrier(): sync_global_devices, not
        # a PS round-trip (the base guard also checks num_workers > 1)
        self._dist = True
        import jax

        from .observability import dist_trace

        # the mesh path has no kvstore server to stamp ranks — the
        # process index IS the rank (fleet timeline / statusz "dist")
        dist_trace.set_rank(jax.process_index())
        from .config import get_flag

        self._zero1 = (get_flag("MXNET_MESH_ZERO1") != 0
                       if zero1 is None else bool(zero1))
        self._bucket_bytes = (self._resolve_bucket_bytes()
                              if bucket_bytes is None
                              else int(bucket_bytes))
        self._key_order = []    # init order drives the bucket layout
        self._plan = None       # list of {"keys", "dtype"} buckets
        self._key_bucket = {}   # key -> bucket index
        self._pending = {}      # key -> locally-reduced grad (stashed)
        self._inflight = {}     # bucket -> (mode, global array, layout)
        self._bucket_seen = {}  # bucket -> frozenset(keys of last cycle)
        self._zero_layout = {}  # bucket -> layout the shard states match
        self._sentinel_tracker = None
        self._sentinel_armed = False
        if dist_trace.sentinel_policy() != "off" and self.num_workers > 1:
            # no server shard 0 to host the comparator: every rank runs
            # its own SentinelTracker over the allgathered fingerprints
            # (same verdict everywhere — the inputs are identical)
            self._sentinel_tracker = dist_trace.SentinelTracker()
            dist_trace.arm_sentinel(self._sentinel_send)
            self._sentinel_armed = True

    # ------------------------------------------------------------ knobs
    def _resolve_bucket_bytes(self):
        from .config import get_flag

        try:
            from . import autotune

            tuned = autotune.lookup("dist.bucket_bytes",
                                    key="dp%d" % self.num_workers)
            if tuned and tuned.get("bucket_bytes"):
                return int(tuned["bucket_bytes"])
        except Exception:
            pass
        return int(get_flag("MXNET_DIST_BUCKET_BYTES"))

    # ------------------------------------------------------- bucket plan
    def init(self, key, value):
        super().init(key, value)
        keys, _vals = _ctype_key_value(key, value)
        self._key_order.extend(keys)
        self._plan = None  # a late init re-cuts the buckets

    def _build_plan(self):
        plan = []
        cur = None
        for k in self._key_order:
            v = self._data[k]
            dt = str(v._data.dtype)
            nbytes = v.size * v._data.dtype.itemsize
            if (cur is None or cur["dtype"] != dt
                    or (cur["bytes"]
                        and cur["bytes"] + nbytes > self._bucket_bytes)):
                cur = {"keys": [], "dtype": dt, "bytes": 0}
                plan.append(cur)
            cur["keys"].append(k)
            cur["bytes"] += nbytes
        self._plan = plan
        self._key_bucket = {k: i for i, b in enumerate(plan)
                            for k in b["keys"]}
        self._bucket_seen = {}

    def _bucket_of(self, k):
        if self._plan is None or k not in self._key_bucket:
            self._build_plan()
        return self._key_bucket[k]

    # ------------------------------------------------------- push / pull
    def _push_impl(self, key, value, priority=0):
        from .observability import perf as _perf

        # the exchange is an in-device collective, not a host RPC: mark
        # the waterfall row so the (tiny) kvstore segment reads as
        # dispatch time of compiled collectives
        _perf.mark_collective()
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            if k not in self._data:
                raise MXNetError("key %r has not been initialized" % (k,))
            merged = self._reduce(vlist)  # local multi-device reduce
            from .ndarray.sparse import BaseSparseNDArray

            if isinstance(merged, BaseSparseNDArray):
                # the mesh wire format is flat dense buckets; sparse
                # grads densify here (dist_sync keeps the nnz wire)
                merged = merged._dense_nd()
            self._pending[k] = merged
            b = self._bucket_of(k)
            seen = self._bucket_seen.get(b)
            if (seen is not None and b not in self._inflight
                    and seen.issubset(self._pending.keys())):
                # steady state: the bucket's key set is known from the
                # last cycle and is now complete — dispatch EAGERLY so
                # this bucket's collective overlaps the rest of backward
                self._dispatch(b)

    def _pull_impl(self, key, out, priority=0):
        from .observability import perf as _perf

        _perf.mark_collective()
        keys, outs = _ctype_key_value(key, out)
        for k, olist in zip(keys, outs):
            if k not in self._data:
                raise MXNetError("key %r has not been initialized" % (k,))
            self._settle(k)
            src = self._data[k]
            for o in olist:
                src.copyto(o)

    def _settle(self, k):
        """Make ``self._data[k]`` reflect every pushed gradient of k's
        bucket (dispatch if still pending, consume if in flight)."""
        if not self._pending and not self._inflight:
            return
        b = self._bucket_of(k)
        # at most two rounds: a stale in-flight bucket is consumed, then
        # the leftover pending keys dispatch as a second partial bucket
        while k in self._pending or b in self._inflight:
            if b in self._inflight:
                self._consume(b)
            if k in self._pending:
                self._dispatch(b)

    def _dispatch(self, b):
        """Fuse the bucket's pending gradients into one flat array and
        launch the cross-process collective (async — this returns as
        soon as the program is enqueued)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        bucket = self._plan[b]
        present = [k for k in bucket["keys"] if k in self._pending]
        if not present:
            return
        n = self.num_workers
        dt = bucket["dtype"]
        layout, pieces, off = [], [], 0
        for k in present:
            g = self._pending.pop(k)
            flat = g._data.reshape(-1)
            size = int(flat.size)
            layout.append((k, off, size, tuple(g.shape)))
            pieces.append(flat)
            off += size
        total = off
        if n == 1:
            flat = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
            self._inflight[b] = ("local", flat, layout, total)
            return
        zero1 = self._zero1 and self._updater is not None
        pad = (-total) % n if zero1 else 0
        if pad:
            pieces.append(jnp.zeros((pad,), dtype=dt))
        flat = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
        mesh = self._reduce_mesh()
        my_dev = mesh.devices.ravel()[jax.process_index()]
        local = jax.device_put(flat[None], my_dev)
        size = total + pad
        gshape = (n, size)
        garr = jax.make_array_from_single_device_arrays(
            gshape, NamedSharding(mesh, PartitionSpec("p")), [local])
        mode = "rs" if zero1 else "ar"
        pkey = (mode, gshape, dt)
        if pkey not in self._psum_progs:
            if mode == "rs":
                # reduce-scatter: the summed gradient lands SHARDED over
                # the process axis — each rank holds rows [r] of (n, s/n)
                shard = size // n
                self._psum_progs[pkey] = jax.jit(
                    lambda a, _n=n, _s=shard: a.sum(axis=0).reshape(_n, _s),
                    out_shardings=NamedSharding(mesh, PartitionSpec("p")))
            else:
                # all-reduce: the sum replicates to every process
                self._psum_progs[pkey] = jax.jit(
                    lambda a: a.sum(axis=0),
                    out_shardings=NamedSharding(mesh, PartitionSpec()))
        out = self._psum_progs[pkey](garr)
        self._inflight[b] = (mode, out, layout, total)

    def _consume(self, b):
        """Fold a finished bucket back into ``self._data`` — run the
        (possibly sharded) optimizer update or store the merged grads."""
        mode, arr, layout, total = self._inflight.pop(b)
        self._bucket_seen[b] = frozenset(k for k, _o, _s, _sh in layout)
        if mode == "rs":
            self._consume_zero1(b, arr, layout, total)
            return
        flat = arr if mode == "local" else arr.addressable_data(0)
        for k, off, size, shape in layout:
            merged = _from_data(flat[off:off + size].reshape(shape),
                                self._data[k].context)
            if self._updater is not None:
                self._updater(_updater_key(k), merged, self._data[k])
            else:
                # update_on_kvstore=False: pull hands back merged grads
                self._data[k] = merged

    def _consume_zero1(self, b, arr, layout, total):
        """ZeRO-1 tail of the exchange: update THIS rank's gradient
        shard with its locally-owned optimizer state, then all-gather
        the updated parameter shards to every rank."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        sig = tuple((k, off, size) for k, off, size, _sh in layout)
        prev = self._zero_layout.get(b)
        if prev is not None and prev != sig:
            raise MXNetError(
                "mesh ZeRO-1 needs a stable pushed-key set per bucket: "
                "bucket %d's layout changed mid-training, so the sharded "
                "optimizer state no longer lines up (push the same keys "
                "every step, or create a fresh kvstore)" % b)
        self._zero_layout[b] = sig
        n = self.num_workers
        rank = self.rank
        shard = int(arr.shape[1])
        lo, hi = rank * shard, (rank + 1) * shard
        gshard = arr.addressable_data(0).reshape(-1)
        dt = self._plan[b]["dtype"]
        pieces = []
        covered = 0
        for k, off, size, _shape in layout:
            s_lo, s_hi = max(off, lo), min(off + size, hi)
            if s_lo >= s_hi:
                continue
            wfull = self._data[k]._data.reshape(-1)
            ctx = self._data[k].context
            w_nd = _from_data(wfull[s_lo - off:s_hi - off], ctx)
            g_nd = _from_data(gshard[s_lo - lo:s_hi - lo], ctx)
            # state for THIS slice only is created/held on this rank:
            # the 1/N optimizer-memory claim is structural, not a cap
            self._updater(_updater_key(k), g_nd, w_nd)
            pieces.append(w_nd._data)
            covered += s_hi - s_lo
        if covered < shard:  # tail rank(s): the pad region carries no key
            pieces.append(jnp.zeros((shard - covered,), dtype=dt))
        buf = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
        mesh = self._reduce_mesh()
        my_dev = mesh.devices.ravel()[rank]
        local = jax.device_put(buf[None], my_dev)
        garr = jax.make_array_from_single_device_arrays(
            (n, shard), NamedSharding(mesh, PartitionSpec("p")), [local])
        pkey = ("ag", (n, shard), dt)
        if pkey not in self._psum_progs:
            self._psum_progs[pkey] = jax.jit(
                lambda a: a.reshape(-1),
                out_shardings=NamedSharding(mesh, PartitionSpec()))
        flat = self._psum_progs[pkey](garr).addressable_data(0)
        for k, off, size, shape in layout:
            self._data[k] = _from_data(
                flat[off:off + size].reshape(shape),
                self._data[k].context)

    # --------------------------------------------------------- sentinel
    def _sentinel_send(self, fp):
        """Fingerprint transport without a server: one small
        ``process_allgather``, every rank compares all ranks. Collective
        — every rank must note the same steps (the synchronous fit loop
        does; the sentinel stays opt-in via MXNET_DIST_SENTINEL)."""
        import numpy as np
        from jax.experimental import multihost_utils

        vals = np.array(
            [0.0 if fp.get(f) is None else float(fp[f])
             for f in _SENTINEL_FIELDS], np.float64)
        mask = np.array(
            [0.0 if fp.get(f) is None else 1.0
             for f in _SENTINEL_FIELDS], np.float64)
        allv = np.asarray(multihost_utils.process_allgather(
            np.concatenate([vals, mask])))
        tracker = self._sentinel_tracker
        nf = len(_SENTINEL_FIELDS)
        mine = int(fp.get("rank", self.rank))
        verdict = None
        # peers first, own fingerprint last: the returned verdict then
        # compares this rank against every peer's newest entry
        rows = sorted(range(allv.shape[0]),
                      key=lambda r: int(allv[r, 0]) == mine)
        for r in rows:
            vrow, mrow = allv[r, :nf], allv[r, nf:]
            pfp = {f: (float(vrow[i]) if mrow[i] else None)
                   for i, f in enumerate(_SENTINEL_FIELDS)}
            pfp["rank"] = int(vrow[0])
            pfp["step"] = int(vrow[1])
            v = tracker.note(pfp)
            if pfp["rank"] == mine:
                verdict = v
        return verdict

    def sentinel_summary(self):
        return (self._sentinel_tracker.summary()
                if self._sentinel_tracker is not None else None)

    # ------------------------------------------------- optimizer states
    def save_optimizer_states(self, fname, dump_optimizer=False):
        """Under ZeRO-1 each rank holds only its shard of the moments:
        every rank's blob is allgathered and ALL of them land in one
        artifact, so any rank's file resumes any rank bit-exact (the
        resilience/checkpoint.py round-trip contract)."""
        if self._updater is None:
            raise MXNetError("set_optimizer() first — the mesh store "
                             "runs updates in-process")
        blob = self._updater.get_states(dump_optimizer)
        if self._zero1 and self.num_workers > 1:
            payload = pickle.dumps({
                "__format__": "mxtpu_mesh_zero1",
                "num_workers": self.num_workers,
                "shards": self._allgather_blobs(blob)})
        else:
            payload = blob
        with open(fname, "wb") as fout:
            fout.write(payload)

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("set_optimizer() first — the mesh store "
                             "runs updates in-process")
        with open(fname, "rb") as fin:
            blob = fin.read()
        try:
            obj = pickle.loads(blob)
        except Exception:
            obj = None
        if isinstance(obj, dict) \
                and obj.get("__format__") == "mxtpu_mesh_zero1":
            if int(obj["num_workers"]) != self.num_workers:
                raise MXNetError(
                    "ZeRO-sharded optimizer states were saved with %d "
                    "workers; this job has %d (shard boundaries would "
                    "not line up)" % (obj["num_workers"],
                                      self.num_workers))
            self._updater.set_states(obj["shards"][self.rank])
        else:
            self._updater.set_states(blob)

    def _allgather_blobs(self, blob):
        import numpy as np
        from jax.experimental import multihost_utils

        data = np.frombuffer(blob, np.uint8)
        lens = np.asarray(multihost_utils.process_allgather(
            np.array([data.size], np.int64))).reshape(-1)
        width = int(lens.max())
        padded = np.zeros(width, np.uint8)
        padded[:data.size] = data
        allb = np.asarray(multihost_utils.process_allgather(padded))
        allb = allb.reshape(self.num_workers, width)
        return [allb[r, :int(lens[r])].tobytes()
                for r in range(self.num_workers)]

    # ------------------------------------------------------------- misc
    def optimizer_state_bytes(self):
        """Host-visible bytes of THIS rank's optimizer state — the
        ZeRO-1 ~1/N-per-chip witness (tools/mesh_smoke.py)."""
        def walk(v):
            data = getattr(v, "_data", None)
            if data is not None:
                return int(data.size) * data.dtype.itemsize
            if isinstance(v, (tuple, list)):
                return sum(walk(x) for x in v)
            size = getattr(v, "nbytes", None)
            return int(size) if size is not None else 0

        states = self._updater.states if self._updater is not None else {}
        return sum(walk(v) for v in states.values())

    def push_staleness(self):
        out = super().push_staleness()
        out["zero1"] = self._zero1
        out["bucket_bytes"] = self._bucket_bytes
        if self._plan is not None:
            out["buckets"] = len(self._plan)
        return out

    def close(self):
        if self._sentinel_armed:
            from .observability import dist_trace

            dist_trace.disarm_sentinel()
            self._sentinel_armed = False
