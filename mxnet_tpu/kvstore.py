"""KVStore — the communication layer (reference: include/mxnet/kvstore.h:47,
src/kvstore/kvstore_local.h, comm.h, python/mxnet/kvstore.py).

The reference implements Push as a device→buffer reduce (CommCPU/CommDevice,
src/kvstore/comm.h:121/512) + optimizer update + Broadcast. On TPU the
aggregation itself is an XLA program: pushed per-device gradients are summed
with one jitted add-n (XLA emits ICI all-reduce-style collectives when the
arrays are sharded), the updater runs as a fused optimizer op, and Pull
returns the merged value. The API surface (init/push/pull/row_sparse_pull,
str/int keys, set_optimizer, rank/num_workers, barrier) matches
python/mxnet/kvstore.py so Module/Trainer code ports unchanged; multi-host
"dist_*" types map onto jax.distributed + global collectives (SURVEY.md §5.8)
via the same facade.
"""
from __future__ import annotations

import os
import pickle
import threading
import time

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray
from . import optimizer as opt
from .resilience import faults as _faults
from .resilience import retry as _retry

__all__ = ["KVStore", "create"]

# chaos-testable injection points (resilience/faults.py): zero-cost
# no-ops unless an MXNET_FAULTS spec matches; a drop here looks exactly
# like a lost socket, which the retry wrapper around push/pull heals
_faults.declare("kvstore.push",
                doc="before one push's reduce+update/RPC — drop faults "
                    "are retried (backoff + shard reconnect)")
_faults.declare("kvstore.pull",
                doc="before one pull's fetch — drop faults are retried")


def _ctype_key_value(keys, vals):
    """Normalize (keys, vals) to parallel flat lists (reference:
    kvstore.py:_ctype_key_value)."""
    if isinstance(keys, (tuple, list)):
        assert len(keys) == len(vals)
        flat_k, flat_v = [], []
        for k, v in zip(keys, vals):
            fk, fv = _ctype_key_value(k, v)
            flat_k.extend(fk)
            flat_v.extend(fv)
        return flat_k, flat_v
    if isinstance(vals, NDArray):
        return [keys], [[vals]]
    for v in vals:
        assert isinstance(v, NDArray)
    return [keys], [list(vals)]


def _ensure_distributed():
    """Initialize jax.distributed from the launcher's env (tools/launch.py
    analog of the reference's DMLC_ROLE/DMLC_PS_ROOT_URI role system,
    src/kvstore/kvstore_dist.h + ps-lite Van)."""
    import jax

    # a second dist-store create must reuse the live client: re-running
    # initialize() after computations have executed trips "must be
    # called before any JAX computations"
    if jax.distributed.is_initialized():
        return
    coord = os.environ.get("MXTPU_COORDINATOR")
    nworkers = os.environ.get("MXTPU_NUM_WORKERS")
    worker_id = os.environ.get("MXTPU_WORKER_ID")
    if coord is None:
        raise MXNetError(
            "dist_* KVStore needs jax.distributed: either call "
            "jax.distributed.initialize() yourself or launch workers with "
            "tools/launch.py (sets MXTPU_COORDINATOR/MXTPU_NUM_WORKERS/"
            "MXTPU_WORKER_ID)")
    try:
        # CPU fake-cluster path (tests/nightly dist pattern); harmless no-op
        # name on TPU backends where collectives ride ICI/DCN natively
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    jax.distributed.initialize(coord, num_processes=int(nworkers),
                               process_id=int(worker_id))


import weakref

_live_stores = weakref.WeakSet()  # every constructed KVStore, GC-pruned


def _stores_staleness():
    """Flight-recorder provider: per-key push staleness of EVERY live
    store — one store dumps as its dict, several as {"stores": [...]}."""
    views = []
    for kv in list(_live_stores):
        try:
            view = kv.push_staleness()
        except Exception as err:
            view = {"error": repr(err)}
        if view:
            views.append(view)
    if not views:
        return None
    return views[0] if len(views) == 1 else {"stores": views}


class KVStore:
    """Key-value store for parameter synchronization."""

    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._data = {}          # key -> merged NDArray (the "server" copy)
        self._push_lock = threading.Lock()
        self._push_stats = {}    # key -> [push count, last push ts]  # guarded-by: self._push_lock
        self._updater = None
        self._optimizer = None
        self._compression_params = None
        self._barrier_count = 0
        self._retry_policy = _retry.RetryPolicy()
        self._dist = kv_type.startswith("dist")
        if self._dist:
            _ensure_distributed()
            # stamp this process's rank onto the perf waterfall ring:
            # the fleet step timeline (observability/dist_trace.py)
            # aligns workers' rows by (rank, step)
            import jax

            from .observability import dist_trace

            dist_trace.set_rank(jax.process_index())
        self._register_health_provider()

    def _register_health_provider(self):
        """Expose per-key push staleness to the crash flight recorder.
        Every live store joins a module-level WeakSet walked by ONE
        'kvstore' provider — a fixed per-instance registration would let
        a later throwaway store shadow the main one, and a weak set never
        pins a dropped store."""
        from .observability import flight_recorder

        _live_stores.add(self)
        flight_recorder.register_provider("kvstore", _stores_staleness)

    def push_staleness(self):
        """{key: {"pushes", "age_s"}} as seen by this worker — the dist
        variants also gather the server-side view."""
        import time as _time

        now = _time.time()
        with self._push_lock:  # a concurrent push must not tear this walk
            stats = {k: tuple(v) for k, v in self._push_stats.items()}
        return {"type": self.type,
                "per_key": {str(k): {"pushes": count,
                                     "age_s": round(now - last_ts, 3)}
                            for k, (count, last_ts) in stats.items()}}

    def _note_push(self, key):
        import time as _time

        with self._push_lock:
            entry = self._push_stats.setdefault(key, [0, 0.0])
            entry[0] += 1
            entry[1] = _time.time()

    # --- basic ops (reference: kvstore.py init/push/pull) -----------------
    def init(self, key, value):
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            if k in self._data:
                raise MXNetError("key %r already initialized" % (k,))
            self._data[k] = vlist[0].copy()

    def _reduce(self, vlist):
        """Sum per-device pushed values — CommDevice::Reduce analog
        (src/kvstore/comm.h:512); one XLA add-n instead of P2P copies.
        Row-sparse pushes merge-sum by index union (ReduceSumCPUExSerial
        analog, comm.h:335)."""
        from .ndarray.sparse import RowSparseNDArray, rsp_add

        if len(vlist) == 1:
            return vlist[0].copy()
        if any(isinstance(v, RowSparseNDArray) for v in vlist):
            merged = vlist[0]
            for v in vlist[1:]:
                merged = rsp_add(merged, v)
            return merged
        return nd.add_n(*vlist)

    def _reduce_mesh(self):
        """One-representative-device-per-process mesh for global reduces."""
        if getattr(self, "_mesh", None) is None:
            from .parallel.mesh import process_mesh

            self._mesh = process_mesh("p")
            self._psum_progs = {}
        return self._mesh

    def _global_reduce(self, merged):
        """Sum the locally-merged value across all worker processes — the
        dist_sync server-side accumulate (kvstore_dist_server.h:261-312) as
        ONE compiled XLA program: each process contributes its shard of a
        cross-process global array and the sum runs as an in-program
        all-reduce over the process axis (ICI/DCN collective on TPU, gloo
        on the CPU fake cluster) — no per-key host round-trip of the full
        gradient (SURVEY.md §5.8 design). Every worker applies the
        identical update, so weights stay bit-identical across workers."""
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        from .ndarray.sparse import BaseSparseNDArray, RowSparseNDArray

        if isinstance(merged, RowSparseNDArray):
            return self._global_reduce_rsp(merged)
        if isinstance(merged, BaseSparseNDArray):
            merged = merged._dense_nd()  # csr: no sparse wire format
        mesh = self._reduce_mesh()
        x = merged._data
        my_dev = mesh.devices.ravel()[jax.process_index()]
        local = jax.device_put(x[None], my_dev)
        gshape = (jax.process_count(),) + tuple(x.shape)
        garr = jax.make_array_from_single_device_arrays(
            gshape, NamedSharding(mesh, PartitionSpec("p")), [local])
        key = (gshape, str(x.dtype))
        if key not in self._psum_progs:
            self._psum_progs[key] = jax.jit(
                lambda a: a.sum(axis=0),
                out_shardings=NamedSharding(mesh, PartitionSpec()))
        out = self._psum_progs[key](garr)
        # the replicated result is already on device; no host round-trip
        from .ndarray.ndarray import _from_data

        return _from_data(out.addressable_data(0), merged.context)

    def _global_reduce_rsp(self, merged):
        """Row-sparse global merge WITHOUT densifying: workers exchange
        only (row-id, values) padded to the global max nnz — the
        EncodeRowSparseKey idea (kvstore_dist.h:444) where wire traffic
        scales with nnz, not the full table."""
        import numpy as np
        from jax.experimental import multihost_utils

        from .ndarray.sparse import row_sparse_array

        idx = np.asarray(merged._aux[0])
        vals = np.asarray(merged._data)
        nnzs = multihost_utils.process_allgather(
            np.array([idx.shape[0]], np.int64))
        # bucket the pad size (next power of two) so the compiled
        # collective count stays bounded as nnz varies per step
        max_nnz = int(nnzs.max())
        max_nnz = 1 << (max_nnz - 1).bit_length() if max_nnz > 1 else 1
        pad = max_nnz - idx.shape[0]
        idx_p = np.concatenate([idx, np.full((pad,), -1, idx.dtype)])
        vals_p = np.concatenate(
            [vals, np.zeros((pad,) + vals.shape[1:], vals.dtype)])
        all_idx = multihost_utils.process_allgather(idx_p)
        all_vals = multihost_utils.process_allgather(vals_p)
        flat_idx = np.asarray(all_idx).reshape(-1)
        flat_vals = np.asarray(all_vals).reshape(
            (-1,) + vals.shape[1:])
        keep = flat_idx >= 0
        ui, inv = np.unique(flat_idx[keep], return_inverse=True)
        out_vals = np.zeros((len(ui),) + vals.shape[1:], vals.dtype)
        np.add.at(out_vals, inv, flat_vals[keep])
        return row_sparse_array((out_vals, ui), shape=merged.shape,
                                ctx=merged.context)

    def push(self, key, value, priority=0):
        from .observability import counter, trace_span

        def _attempt():
            # this retry layer heals drops injected at the OPERATION
            # level (and, for local stores, any connection-shaped error
            # — local pushes have no inner transport). Dist stores'
            # real socket losses are healed one level down, by
            # PSClient._call's retry-through-reconnect; inject at
            # `kvstore.rpc` to chaos-test that path. Only
            # connection-shaped errors are retried — a semantic error
            # (uninitialized key) stays fatal, and an exhausted inner
            # retry (RetryExhaustedError) is not re-retried here.
            _faults.inject("kvstore.push")
            self._push_impl(key, value, priority)

        from .observability import request_trace as _rtrace

        ambient = _rtrace.current()
        if ambient is not None:
            # close the caller's interval as the push STARTS — the
            # "kvstore.push" phase below then covers exactly the RPC,
            # not all the compute since the trace's previous mark
            ambient.event("step")
        from .observability import perf as _perf

        _t_kv = time.perf_counter()
        with trace_span("kvstore.push", "kvstore"):
            _retry.call(_attempt, policy=self._retry_policy,
                        name="kvstore.push")
        # kvstore/collective segment of the fit-step waterfall (no-op
        # outside a perf step scope)
        _perf.note_kv(time.perf_counter() - _t_kv)
        counter("kvstore.push").inc()
        if ambient is not None:
            # this push is one of the ambient trace's phases (the dist
            # RPC under it already carried the trace id — PSClient._call)
            ambient.event("kvstore.push")
        for k in (key if isinstance(key, (list, tuple)) else (key,)):
            self._note_push(k)

    def _push_impl(self, key, value, priority=0):
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            if k not in self._data:
                raise MXNetError("key %r has not been initialized" % (k,))
            merged = self._reduce(vlist)
            from .ndarray.sparse import BaseSparseNDArray as _Sp

            if self._gc_active() and not isinstance(merged, _Sp):
                # quantize the locally-merged gradient; dist wire carries
                # the packed 2-bit codes (kvstore_dist.h:346 Quantize)
                import numpy as np

                codes = self._quantize_2bit(k, merged)
                if self._dist and self.num_workers > 1:
                    from jax.experimental import multihost_utils

                    packed = self._pack_2bit(codes)
                    all_packed = np.asarray(
                        multihost_utils.process_allgather(packed))
                    deq = sum(self._unpack_2bit(p, codes.size)
                              .astype(np.float32)
                              for p in all_packed)
                    merged = nd.array(
                        (deq * self._gc_threshold).reshape(codes.shape)
                        .astype(merged.dtype), ctx=merged.context)
                else:
                    merged = nd.array(
                        (codes.astype(np.float32) * self._gc_threshold)
                        .astype(merged.dtype), ctx=merged.context)
            elif self._dist and self.num_workers > 1:
                merged = self._global_reduce(merged)
            if self._updater is not None:
                from .ndarray.sparse import BaseSparseNDArray

                if isinstance(self._data[k], BaseSparseNDArray):
                    # the updater's lazy-row path indexes the weight by
                    # absolute row id, which is only valid for dense
                    # storage — densify the stored value first (reference
                    # servers keep dense weights too,
                    # kvstore_dist_server.h DataHandleDefault)
                    self._data[k] = self._data[k]._dense_nd()
                self._updater(_updater_key(k), merged, self._data[k])
            else:
                # reference semantics: push REPLACES the stored value with the
                # merged result (src/kvstore/kvstore_local.h PushImpl);
                # accumulating would corrupt update_on_kvstore=False training
                self._data[k] = merged

    def pull(self, key, out=None, priority=0):
        from .observability import counter, trace_span

        assert out is not None

        def _attempt():
            _faults.inject("kvstore.pull")
            self._pull_impl(key, out, priority)

        from .observability import request_trace as _rtrace

        ambient = _rtrace.current()
        if ambient is not None:
            ambient.event("step")  # pull phase starts here, not at the
            #                        trace's previous mark
        from .observability import perf as _perf

        _t_kv = time.perf_counter()
        with trace_span("kvstore.pull", "kvstore"):
            _retry.call(_attempt, policy=self._retry_policy,
                        name="kvstore.pull")
        _perf.note_kv(time.perf_counter() - _t_kv)
        counter("kvstore.pull").inc()
        if ambient is not None:
            ambient.event("kvstore.pull")

    def _pull_impl(self, key, out, priority=0):
        keys, outs = _ctype_key_value(key, out)
        for k, olist in zip(keys, outs):
            if k not in self._data:
                raise MXNetError("key %r has not been initialized" % (k,))
            src = self._data[k]
            for o in olist:
                src.copyto(o)  # NDArray.copyto casts storage when needed

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows as row_sparse (reference:
        KVStoreDist::PullRowSparseImpl kvstore_dist.h:258 — per-row-id
        server fetch; here a gather from the stored value)."""
        from .ndarray.sparse import (BaseSparseNDArray, RowSparseNDArray,
                                     row_sparse_array, sparse_retain)

        assert out is not None
        if row_ids is None:
            self.pull(key, out=out, priority=priority)
            return
        keys, outs = _ctype_key_value(key, out)
        if not isinstance(row_ids, (tuple, list)):
            row_ids = [row_ids] * len(keys)
        for k, olist, rids in zip(keys, outs, row_ids):
            if k not in self._data:
                raise MXNetError("key %r has not been initialized" % (k,))
            src = self._data[k]
            rid_list = rids if isinstance(rids, (tuple, list)) else [rids]
            if len(rid_list) == 1 and len(olist) > 1:
                rid_list = rid_list * len(olist)
            for o, rid in zip(olist, rid_list):
                import numpy as _np

                want = _np.unique(_np.asarray(
                    rid.asnumpy() if isinstance(rid, NDArray) else rid,
                    dtype=_np.int64).reshape(-1))
                if len(want) and (want[0] < 0 or
                                  want[-1] >= src.shape[0]):
                    raise MXNetError(
                        "row_ids out of range for key %r: [%d, %d] vs "
                        "%d rows" % (k, want[0], want[-1], src.shape[0]))
                if isinstance(src, RowSparseNDArray):
                    res = sparse_retain(src, want)
                else:
                    # device-side gather of just the requested rows — no
                    # full-table D2H (the dist analog pulls per-row keys,
                    # kvstore_dist.h:258); `want` is sorted/unique already
                    import jax.numpy as _jnp

                    from .ndarray.sparse import _sparse_new

                    rows = src._data[_jnp.asarray(want)]
                    res = _sparse_new(RowSparseNDArray, rows,
                                      (_jnp.asarray(want),), src.shape,
                                      src.context)
                if isinstance(o, BaseSparseNDArray):
                    res.copyto(o)
                else:
                    o._set_data(res._dense_nd()._data.astype(o._data.dtype))

    # --- optimizer wiring (reference: kvstore.py:set_optimizer) ------------
    def set_optimizer(self, optimizer):
        # The reference pickles the optimizer to dist servers
        # (kvstore.py:419-460); locally it installs an updater.
        self._optimizer = optimizer
        self._set_updater(opt.get_updater(optimizer))

    def _set_updater(self, updater):
        self._updater = updater

    set_updater = _set_updater

    def set_gradient_compression(self, compression_params):
        """2-bit gradient compression with error feedback (reference:
        src/kvstore/gradient_compression.h:37-52, quantize_2bit kernel in
        gradient_compression-inl.h:44-80): each push quantizes
        residual+grad to {-threshold, 0, +threshold}, keeping the
        quantization error in a per-key residual. On dist stores the wire
        carries the packed 2-bit codes (16x smaller than fp32)."""
        ctype = (compression_params or {}).get("type")
        if ctype not in (None, "none", "2bit"):
            raise MXNetError("unsupported gradient compression %r "
                             "(reference supports '2bit' only)" % ctype)
        self._compression_params = compression_params
        self._gc_threshold = float(
            (compression_params or {}).get("threshold", 0.5))
        if ctype == "2bit" and self._gc_threshold <= 0:
            raise MXNetError("2bit compression needs threshold > 0, got %g"
                             % self._gc_threshold)
        self._gc_residuals = {}

    def _gc_active(self):
        return (self._compression_params or {}).get("type") == "2bit"

    def _quantize_2bit(self, key, merged):
        """residual += grad; emit codes in {-1, 0, +1}; residual keeps the
        quantization error (quantize_2bit Map, gradient_compression-inl.h)."""
        import numpy as np

        t = self._gc_threshold
        g = merged.asnumpy().astype(np.float32)
        buf = self._gc_residuals.setdefault(key, np.zeros(g.shape,
                                                          np.float32))
        buf += g
        codes = np.zeros(g.shape, np.int8)
        codes[buf >= t] = 1
        codes[buf <= -t] = -1
        buf -= codes * t
        return codes

    @staticmethod
    def _pack_2bit(codes):
        """Four 2-bit fields per byte (00 zero, 11 pos, 10 neg) — the
        reference wire layout (posbits/negbits masks)."""
        import numpy as np

        flat = codes.reshape(-1)
        pad = (-len(flat)) % 4
        flat = np.concatenate([flat, np.zeros(pad, np.int8)])
        field = np.where(flat == 1, 3, np.where(flat == -1, 2, 0)) \
            .astype(np.uint8).reshape(-1, 4)
        shifts = np.array([6, 4, 2, 0], np.uint8)
        return (field << shifts).sum(axis=1).astype(np.uint8)

    @staticmethod
    def _unpack_2bit(packed, n):
        import numpy as np

        shifts = np.array([6, 4, 2, 0], np.uint8)
        fields = (packed[:, None] >> shifts) & 0x3
        flat = fields.reshape(-1)[:n]
        return np.where(flat == 3, 1, np.where(flat == 2, -1, 0)) \
            .astype(np.int8)

    # --- distributed attributes (reference: kvstore.py rank/num_workers) ---
    @property
    def rank(self):
        import jax
        return jax.process_index()

    @property
    def num_workers(self):
        import jax
        return jax.process_count()

    def _barrier(self):
        self._barrier_count += 1
        if self._dist and self.num_workers > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(
                "kvstore_barrier_%d" % self._barrier_count)

    barrier = _barrier

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "Cannot save states for distributed training"
        with open(fname, "wb") as fout:
            fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "Cannot load states for distributed training"
        with open(fname, "rb") as fin:
            self._updater.set_states(fin.read())

    def _send_command_to_servers(self, head, body):
        # the reference ships pickled optimizer commands to PS servers
        # (python/mxnet/kvstore.py:419-460); this build runs server logic
        # in-process, so a silent no-op would hide real misuse.
        # KVStoreDistAsync overrides this with the real server RPC.
        raise MXNetError(
            "_send_command_to_servers is a parameter-server RPC; this "
            "kvstore type (%r) runs updates in-process — use "
            "set_optimizer() instead" % (self.type,))

    def get_num_dead_node(self, node_id=0, timeout=60):
        """Liveness query (reference: include/mxnet/kvstore.h:338
        get_num_dead_node over ps-lite heartbeats). Non-PS stores run
        every role in this process, so nothing can be dead."""
        return 0


class KVStoreDistAsync(KVStore):
    """``dist_async`` — the reference's asynchronous parameter server
    (src/kvstore/kvstore_dist_server.h:422-435: each worker's push updates
    server weights immediately; no cross-worker synchronization, straggler
    tolerant by design).

    There is no XLA-collective analog of asynchrony — a compiled psum IS a
    synchronization point — so this runs the reference's actual host-side
    architecture: TCP parameter servers (mxnet_tpu/kvstore_server.py)
    holding the weights, with the optimizer shipped from rank 0 as a
    pickle (_send_command_to_servers head 0). Device compute (forward/
    backward) stays on-chip; push/pull move gradients/weights host-side
    per key, exactly the reference's wire pattern.
    """

    def __init__(self):
        # intentionally NOT calling super().__init__ with dist machinery:
        # the PS path needs no jax.distributed (workers only talk to
        # servers; no worker-to-worker collectives)
        self.type = "dist_async"
        self._data = {}
        self._updater = None
        self._optimizer = None
        self._compression_params = None
        self._barrier_count = 0
        self._retry_policy = _retry.RetryPolicy()
        self._dist = True
        addrs = os.environ.get("MXTPU_PS_ADDR")
        self._rank = int(os.environ.get("MXTPU_WORKER_ID", "0"))
        self._num_workers = int(os.environ.get("MXTPU_NUM_WORKERS", "1"))
        self._own_server = None
        if not addrs:
            # single-process convenience: spin up an in-process server so
            # dist_async works without a launcher (and its update/pull
            # semantics can be unit-tested)
            from .kvstore_server import start_server_thread

            self._own_server = start_server_thread()
            addrs = self._own_server.address
        from .kvstore_server import PSClient

        self._client = PSClient(addrs.split(","), self._rank)
        self._key_shapes = {}
        # big-array slicing bound (elements): values larger than this are
        # split across ALL server shards instead of hashing to one, so a
        # single fat fc/embedding weight cannot hot-spot one server
        # (reference: kvstore_dist.h:147,229 EncodeDefaultKey slicing,
        # MXNET_KVSTORE_BIGARRAY_BOUND)
        self._bigarray_bound = int(os.environ.get(
            "MXNET_KVSTORE_BIGARRAY_BOUND", str(10 ** 6)))
        self._big_plans = {}  # key -> list of (subkey, shard, lo, hi)
        self._push_lock = threading.Lock()
        self._push_stats = {}  # guarded-by: self._push_lock
        self._register_health_provider()
        from .observability import dist_trace

        dist_trace.set_rank(self._rank)
        self._sentinel_armed = False
        if dist_trace.sentinel_policy() != "off":
            # every rank's per-step fingerprint must meet on ONE
            # comparator: shard 0 hosts the SentinelTracker, and the
            # verdict rides back on the reply (no extra round trip)
            client = self._client
            dist_trace.arm_sentinel(
                lambda fp: client.call0(("sentinel", fp)))
            self._sentinel_armed = True

    def push_staleness(self):
        """Worker-side view plus every server shard's per-key push
        staleness (kvstore_server health op) — the section the flight
        recorder embeds so a dump shows which keys stopped flowing.

        This runs inside the CRASH-DUMP path (excepthook/atexit), so it
        must be bounded: a plain ``gather_call`` would block forever on a
        shard's socket lock if another thread is parked in a long server
        barrier, hanging the dying process inside its own crash handler.
        Every lock acquire and socket read here carries a short timeout;
        a busy or dead shard becomes an ``error`` entry, never a hang."""
        from .kvstore_server import _recv_msg, _send_msg

        out = super().push_staleness()
        servers = []
        client = self._client
        for i in range(client.num_shards):
            lock = client._locks[i]
            if not lock.acquire(timeout=2.0):
                servers.append({"error": "shard busy (lock timeout)"})
                continue
            try:
                sock = client._socks[i]
                old_timeout = sock.gettimeout()
                sock.settimeout(5.0)
                try:
                    _send_msg(sock, ("health",))
                    resp = _recv_msg(sock)
                    sock.settimeout(old_timeout)
                    servers.append(resp[1] if resp[0] == "ok"
                                   else {"error": resp[1]})
                except Exception as err:
                    servers.append({"error": repr(err)})
                    # a timed-out exchange leaves the (late) health reply
                    # queued on the length-prefixed stream — the NEXT
                    # push/pull would read it as its own response and
                    # silently corrupt a pull. Drop the socket and try
                    # one quick reconnect; if that fails the next data
                    # call errors loudly instead of desyncing.
                    client.reconnect_shard(i, locked=True)
            except Exception as err:  # dead shard must not sink the dump
                servers.append({"error": repr(err)})
            finally:
                lock.release()
        out["servers"] = servers
        return out

    def _slice_plan(self, key, shape):
        """Contiguous flat-slice layout of a big value across all shards
        (None when the value stays on the single hashed shard)."""
        if key in self._big_plans:
            return self._big_plans[key]
        size = 1
        for d in shape:
            size *= int(d)
        shards = self._client.num_shards
        if shards < 2 or size < self._bigarray_bound:
            self._big_plans[key] = None
            return None
        bounds = [size * i // shards for i in range(shards + 1)]
        plan = [("%s#%d" % (key, i), i, bounds[i], bounds[i + 1])
                for i in range(shards) if bounds[i + 1] > bounds[i]]
        self._big_plans[key] = plan
        return plan

    def init(self, key, value):
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            v = vlist[0]
            from .ndarray.sparse import BaseSparseNDArray

            if isinstance(v, BaseSparseNDArray):
                v = v._dense_nd()
            host = v.asnumpy()
            plan = self._slice_plan(k, host.shape)
            if plan:
                flat = host.reshape(-1)
                for subkey, shard, lo, hi in plan:
                    self._client.shard_call(shard,
                                            ("init", subkey, flat[lo:hi]))
            else:
                self._client.key_call(k, ("init", k, host))
            self._key_shapes[k] = v.shape

    def _push_impl(self, key, value, priority=0):
        # the base KVStore.push wraps this with the kvstore.push
        # span + counter; only the implementation is overridden here
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            merged = self._reduce(vlist)   # local multi-device reduce
            from .ndarray.sparse import BaseSparseNDArray

            was_sparse = isinstance(merged, BaseSparseNDArray)
            if was_sparse:
                merged = merged._dense_nd()
            # mirror the dist_sync store: 2-bit compression never applies
            # to sparse gradients (densify-then-compress would silently
            # change semantics for the same inputs)
            plan = self._big_plans.get(k)
            if plan:
                # sliced path: each shard owns a contiguous flat slice and
                # runs the optimizer on it independently (compression is
                # per-slice so error feedback stays shard-local)
                flat = merged.asnumpy().reshape(-1)
                for subkey, shard, lo, hi in plan:
                    piece = flat[lo:hi]
                    if self._gc_active() and not was_sparse:
                        codes = self._quantize_2bit(subkey, nd.array(piece))
                        packed = self._pack_2bit(codes)
                        self._client.shard_call(
                            shard, ("push_2bit", subkey, packed.tobytes(),
                                    codes.size, codes.shape,
                                    self._gc_threshold))
                    else:
                        self._client.shard_call(shard,
                                                ("push", subkey, piece))
                continue
            if self._gc_active() and not was_sparse:
                # quantize with error feedback and send PACKED 2-bit codes
                # (4/byte — the 16x wire saving is the feature's point,
                # kvstore_dist.h:346); the server dequantizes and applies
                # the {0, ±threshold} gradient
                codes = self._quantize_2bit(k, merged)
                packed = self._pack_2bit(codes)
                self._client.key_call(
                    k, ("push_2bit", k, packed.tobytes(), codes.size,
                        codes.shape, self._gc_threshold))
            else:
                self._client.key_call(k, ("push", k, merged.asnumpy()))

    def _pull_impl(self, key, out, priority=0):
        # the base KVStore.pull wraps this with the kvstore.pull
        # span + counter; only the implementation is overridden here
        keys, outs = _ctype_key_value(key, out)
        import numpy as _np

        for k, olist in zip(keys, outs):
            plan = self._big_plans.get(k)
            if plan:
                pieces = [self._client.shard_call(shard, ("pull", subkey))
                          for subkey, shard, _lo, _hi in plan]
                arr = _np.concatenate(
                    [p.reshape(-1) for p in pieces]).reshape(
                        self._key_shapes[k])
            else:
                arr = self._client.key_call(k, ("pull", k))
            src = nd.array(arr)
            for o in olist:
                src.copyto(o)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        from .ndarray.sparse import (BaseSparseNDArray, RowSparseNDArray,
                                     row_sparse_array)

        assert out is not None
        if row_ids is None:
            return self.pull(key, out=out, priority=priority)
        keys, outs = _ctype_key_value(key, out)
        if not isinstance(row_ids, (tuple, list)):
            row_ids = [row_ids] * len(keys)
        import numpy as _np

        for k, olist, rids in zip(keys, outs, row_ids):
            rid_list = rids if isinstance(rids, (tuple, list)) else [rids]
            if len(rid_list) == 1 and len(olist) > 1:
                rid_list = rid_list * len(olist)
            for o, rid in zip(olist, rid_list):
                want = _np.unique(_np.asarray(
                    rid.asnumpy() if isinstance(rid, NDArray) else rid,
                    dtype=_np.int64).reshape(-1))
                shape = self._key_shapes.get(k)
                if shape and len(want) and (want[0] < 0
                                            or want[-1] >= shape[0]):
                    raise MXNetError("row_ids out of range for key %r"
                                     % (k,))
                rows, got = self._client.key_call(
                    k, ("row_sparse_pull", k, want)), want
                res = row_sparse_array((rows, got),
                                       shape=shape or o.shape,
                                       ctx=o.context)
                if isinstance(o, BaseSparseNDArray):
                    res.copyto(o)
                else:
                    o._set_data(
                        res._dense_nd()._data.astype(o._data.dtype))

    # --- server-side optimizer (the PS contract) -------------------------
    def set_optimizer(self, optimizer):
        """Rank 0 ships the pickled optimizer to every server; other
        ranks just barrier alongside (reference: kvstore.py:419-460)."""
        self._optimizer = optimizer
        if self.rank == 0:
            self._send_command_to_servers(0, pickle.dumps(optimizer))
        self._barrier()

    def refresh_optimizer(self, optimizer):
        """Barrier-free hyperparameter re-ship.

        Unlike set_optimizer this may be called from ANY rank and does not
        synchronize workers: dist_async workers are deliberately
        unsynchronized, so a barriered re-ship triggered asymmetrically
        (rank-0-only LR schedule, per-rank rescale_grad) would hang the
        other ranks. The server-side swap preserves optimizer state and is
        idempotent, so duplicate re-ships from several ranks are safe."""
        self._optimizer = optimizer
        self._send_command_to_servers(0, pickle.dumps(optimizer))

    def _send_command_to_servers(self, head, body):
        self._client.all_call(("command", head, body))

    def set_updater(self, updater):
        raise MXNetError("dist_async runs the optimizer on the servers; "
                         "use set_optimizer (reference: update_on_kvstore "
                         "is mandatory for dist_async, "
                         "python/mxnet/model.py _create_kvstore)")

    _set_updater = set_updater

    # --- distributed attributes ------------------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    def _barrier(self):
        self._barrier_count += 1
        if self._num_workers > 1 or self._own_server is None:
            self._client.call0(("barrier", self._num_workers))

    barrier = _barrier

    def get_num_dead_node(self, node_id=0, timeout=60):
        return int(self._client.call0(("num_dead", timeout)))

    def save_optimizer_states(self, fname, dump_optimizer=False):
        # each server shard holds state only for its own keys — gather
        # every shard's blob (a single-shard save would silently lose
        # momentum for keys hashed to the other shards)
        blobs = self._client.gather_call(("save_states",))
        with open(fname, "wb") as fout:
            pickle.dump({"num_shards": len(blobs), "blobs": blobs}, fout)

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as fin:
            data = pickle.load(fin)
        if data["num_shards"] != self._client.num_shards:
            raise MXNetError(
                "optimizer states were saved with %d PS shards; this job "
                "has %d (key->shard placement would not line up)"
                % (data["num_shards"], self._client.num_shards))
        for i, blob in enumerate(data["blobs"]):
            self._client.shard_call(i, ("load_states", blob))

    def close(self):
        if self._sentinel_armed:
            from .observability import dist_trace

            dist_trace.disarm_sentinel()
        if self._own_server is not None:
            self._own_server.stop()
        self._client.close()


def _updater_key(key):
    try:
        return int(key)
    except (TypeError, ValueError):
        return key


def create(name="local"):
    """Create a KVStore (reference: src/kvstore/kvstore.cc:38-76 factory;
    python/mxnet/kvstore.py:create).

    local / local_allreduce_cpu / local_allreduce_device / device / nccl all
    map to the in-process XLA reduce; dist_sync / dist_device_sync require
    jax.distributed (allreduce across worker processes); dist_async talks
    to host-side parameter servers (mxnet_tpu/kvstore_server.py) with the
    optimizer running server-side per push — the reference's asynchronous
    PS architecture; mesh is the collectives-backed sharded-training
    backend (bucketed in-program all-reduce / ZeRO-1 reduce-scatter, zero
    host RPCs on the step path — mxnet_tpu/kvstore_mesh.py,
    docs/distributed.md)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    known = ("local", "local_allreduce_cpu", "local_allreduce_device",
             "device", "nccl", "dist_sync", "dist_async", "dist_device_sync",
             "dist", "mesh")
    if name not in known:
        raise MXNetError("unknown KVStore type %r" % name)
    if name == "dist_async":
        return KVStoreDistAsync()
    if name == "mesh":
        from .kvstore_mesh import KVStoreMesh

        return KVStoreMesh()
    return KVStore(name)
