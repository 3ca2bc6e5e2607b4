"""Cross-process ICI shuffle: the in-process collective epochs of
`shuffle/ici.py`, run as ONE SPMD program over the multi-host mesh.

Every gang member (one worker process per mesh row) executes the same
plan; each `TpuShuffleExchangeExec` binds to a `GangIciShuffleTransport`
and contributes its local map blocks. The collective epoch is then:

1. **manifest barrier** (once per shuffle id) — each member publishes
   its local sizing (block count, caps, var-width buckets, schema
   fingerprint) to the exchange's rendezvous dir; everyone adopts the
   field-wise MAXIMA, so all members enter identical jit programs with
   identical static shapes — the SPMD contract. Zero-block members
   participate with empty slots (schema via `set_shuffle_schema`).
2. **host-boundary assembly** — each member's packed lane stacks
   (L local slots) become rows of one GLOBAL array via
   `jax.make_array_from_process_local_data`; the existing
   `make_ici_all_to_all` kernel then routes rows across the process
   boundary exactly as it routes them across local devices — the
   hierarchical (dcn, ici) axes map inter-process x intra-process hops
   onto the matching interconnect.
3. **local readback** — results come back through each member's
   addressable shards only (a `device_get` of the global array would
   span non-addressable devices); partition p lands on global device
   p mod D, so exactly one member owns and emits it.

Shuffle identity across processes is the transport's own REGISTRATION
ordinal, not the module-global shuffle-id counter: registration order
follows plan structure, which is identical on every member; per-process
id counters drift on long-lived workers.
"""
from __future__ import annotations

import itertools
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..columnar.batch import TpuBatch, bucket_bytes, bucket_rows
from ..lifecycle import QueryCancelled
from ..obs.metrics import REGISTRY as _METRICS
from ..obs.recorder import RECORDER as _FLIGHT
from ..shuffle.ici import (IciShuffleTransport, _discover_epoch_caps,
                           _lane_layout, _lane_spec, _len_lane_indices,
                           _node_at, _pack_block, _pad1, _unpack_device)
from ..shuffle.transport import FetchFailure
from .runtime import MeshRuntime

__all__ = ["GangIciShuffleTransport"]

MESH_COLLECTIVE_EPOCHS = _METRICS.counter(
    "rapids_mesh_collective_epochs_total",
    "Cross-process all-to-all epochs run by the gang shuffle.")
MESH_COLLECTIVE_BYTES = _METRICS.counter(
    "rapids_mesh_collective_bytes_total",
    "Bytes this process contributed to cross-process collective "
    "epochs (packed lane stacks, structural — not wall-clock).")

_BARRIER_POLL_S = 0.005


def _enc(key: Tuple[int, tuple]) -> str:
    ci, path = key
    return f"{ci}:" + ".".join(str(p) for p in path)


def _dec(s: str) -> Tuple[int, tuple]:
    ci, _, path = s.partition(":")
    return int(ci), tuple(int(p) for p in path.split(".") if p != "")


def _max_merge(dicts: List[Dict[str, int]]) -> Dict[tuple, int]:
    out: Dict[tuple, int] = {}
    for d in dicts:
        for k, v in d.items():
            kk = _dec(k)
            out[kk] = max(out.get(kk, 0), int(v))
    return out


def _schema_fp(schema) -> str:
    return ";".join(f"{f.name}:{f.dtype.simple_string()}"
                    for f in schema.fields)


class GangIciShuffleTransport(IciShuffleTransport):
    """`IciShuffleTransport` whose mesh spans N worker processes.

    Single-process runtimes (the graceful fallback) delegate straight
    to the base class — same kernels, no barrier, no rendezvous I/O.
    """

    def __init__(self, runtime: MeshRuntime, exchange_root: str,
                 conf=None, qctx=None):
        super().__init__(runtime.mesh, axis=runtime.axis, conf=conf)
        self._passthrough_excs = (IciShuffleTransport._passthrough_excs
                                  + (QueryCancelled,))
        self._rt = runtime
        self._root = exchange_root
        self._qctx = qctx
        from ..config import MESH_BARRIER_TIMEOUT, RapidsConf
        self._barrier_timeout = (conf or RapidsConf()).get(
            MESH_BARRIER_TIMEOUT)
        self._ord_seq = itertools.count()
        self._ordinals: Dict[int, int] = {}
        self._schemas: Dict[int, object] = {}

    # -- identity / metadata ----------------------------------------------

    def register_shuffle(self, shuffle_id: int, num_partitions: int):
        super().register_shuffle(shuffle_id, num_partitions)
        with self._lock:
            if shuffle_id not in self._ordinals:
                self._ordinals[shuffle_id] = next(self._ord_seq)

    def set_shuffle_schema(self, shuffle_id: int, schema) -> None:
        """Exchange-declared output schema: lets a member with ZERO
        local blocks still pack empty slots and join the collective."""
        with self._lock:
            self._schemas[shuffle_id] = schema

    def partition_stats(self, shuffle_id: int, free_only: bool = False):
        # per-process stats diverge across members; a divergent AQE
        # replan would break the identical-program contract
        if self._rt.distributed:
            return None
        return super().partition_stats(shuffle_id, free_only=free_only)

    def stage_bytes(self, shuffle_id: int):
        # same divergence hazard: the AQE join-strategy switch compares
        # this against a threshold, and members must pick one strategy
        if self._rt.distributed:
            return None
        return super().stage_bytes(shuffle_id)

    def _owns_partition(self, partition_id: int, nparts: int) -> bool:
        if not self._rt.distributed:
            return True
        g = partition_id % self.ndev if nparts != self.ndev \
            else partition_id
        return self._rt.owns_device(g)

    def _check_cancel(self) -> None:
        if self._qctx is not None:
            self._qctx.check()

    # -- the gang collective ----------------------------------------------

    def _realize(self, sid: int):
        if not self._rt.distributed:
            return super()._realize(sid)
        with self._lock:
            if sid in self._results:
                return
            blocks = list(self._pending.get(sid, []))
            nparts = self._nparts.get(sid, self.ndev)
        blocks.sort(key=lambda e: e[0])
        t0 = time.perf_counter()
        results: List[List[TpuBatch]] = [[] for _ in range(nparts)]
        plan = self._gang_plan(sid, blocks, nparts)
        if plan is not None:
            schema, epochs, cap, widths, char_caps, src_caps = plan
            L = self._rt.local_devices
            for e in range(epochs):
                self._check_cancel()
                self._run_gang_epoch(
                    blocks[e * L:(e + 1) * L], schema, nparts, cap,
                    widths, char_caps, src_caps, results, sid, e)
            if blocks:
                from ..shuffle.host import (SHUF_BYTES_WRITTEN,
                                            SHUF_FETCH_WAIT,
                                            SHUF_PARTS_WRITTEN)
                SHUF_FETCH_WAIT.labels("ici").observe(
                    time.perf_counter() - t0)
                SHUF_PARTS_WRITTEN.labels("ici").inc(len(blocks))
                SHUF_BYTES_WRITTEN.labels("ici").inc(
                    sum(b.device_size_bytes() for _, b, _ in blocks))
        with self._lock:
            self._results[sid] = results
            self._pending.pop(sid, None)

    def _gang_plan(self, sid: int, blocks, nparts: int):
        """Publish this member's sizing manifest, wait for all N, adopt
        the global maxima. Returns None when the WHOLE gang has zero
        blocks (nothing to exchange), else
        (schema, epochs, cap, widths, char_caps, src_caps)."""
        schema = blocks[0][1].schema if blocks \
            else self._schemas.get(sid)
        spec = _lane_spec(schema) if schema is not None else None
        fold = nparts != self.ndev
        if blocks:
            widths, char_caps = _discover_epoch_caps(
                blocks, spec, self.ndev, fold, self._jit_widths)
            src_caps = {}
            for ci, path, kind, _ in spec:
                if kind == "str_mat":
                    src_caps[(ci, path)] = bucket_bytes(max(
                        [int(_node_at(b.column(ci), path).chars.shape[0])
                         for _, b, _ in blocks] + [1]), minimum=16)
        else:
            widths, char_caps, src_caps = {}, {}, {}
        man = {"process_id": self._rt.process_id,
               "nblocks": len(blocks),
               "cap": max([b.capacity for _, b, _ in blocks] + [1]),
               "nparts": int(nparts),
               "schema_fp": _schema_fp(schema) if schema is not None
               else "",
               "widths": {_enc(k): int(v) for k, v in widths.items()},
               "char_caps": {_enc(k): int(v)
                             for k, v in char_caps.items()},
               "src_caps": {_enc(k): int(v)
                            for k, v in src_caps.items()}}
        mans = self._barrier(sid, man)
        total = sum(m["nblocks"] for m in mans)
        if total == 0:
            return None
        fps = {m["schema_fp"] for m in mans if m["schema_fp"]}
        if len(fps) > 1:
            raise FetchFailure(
                sid, None, self._xdir(sid), "corrupt",
                f"gang members disagree on the exchange schema: {fps}")
        if {m["nparts"] for m in mans} != {int(nparts)}:
            raise FetchFailure(
                sid, None, self._xdir(sid), "corrupt",
                "gang members disagree on the partition count")
        if schema is None:
            raise FetchFailure(
                sid, None, self._xdir(sid), "io",
                "member has blocks nowhere to learn the schema from "
                "and the exchange never declared one")
        L = self._rt.local_devices
        epochs = max(-(-m["nblocks"] // L) for m in mans)
        cap = max(m["cap"] for m in mans)
        g_widths = _max_merge([m["widths"] for m in mans])
        g_chars = _max_merge([m["char_caps"] for m in mans])
        g_src = _max_merge([m["src_caps"] for m in mans])
        return schema, epochs, cap, g_widths, g_chars, g_src

    def _xdir(self, sid: int) -> str:
        return os.path.join(self._root, f"x{self._ordinals[sid]}")

    def _barrier(self, sid: int, man: Dict) -> List[Dict]:
        """One filesystem rendezvous per shuffle id: every member's
        manifest, or a classified io failure on timeout. Polls the
        query's cancel token so a cancelled member exits the barrier
        (and, via the shared cancel marker, frees the others too)."""
        xdir = self._xdir(sid)
        os.makedirs(xdir, exist_ok=True)
        path = os.path.join(xdir, f"m{self._rt.process_id}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(man, f)
        os.replace(path + ".tmp", path)
        n = self._rt.num_processes
        deadline = time.monotonic() + self._barrier_timeout
        mans: Dict[int, Dict] = {}
        while True:
            self._check_cancel()
            for k in range(n):
                if k in mans:
                    continue
                try:
                    with open(os.path.join(xdir, f"m{k}.json")) as f:
                        mans[k] = json.load(f)
                except (OSError, json.JSONDecodeError):
                    pass
            if len(mans) == n:
                return [mans[k] for k in range(n)]
            if time.monotonic() > deadline:
                raise FetchFailure(
                    sid, None, xdir, "io",
                    f"mesh manifest barrier timed out after "
                    f"{self._barrier_timeout:.0f}s "
                    f"({len(mans)}/{n} members present)")
            time.sleep(_BARRIER_POLL_S)

    def _global(self, stack):
        """Local (L, ...) lane stack -> rows of the (D, ...) global
        array: the per-process addressable-shard assembly at the host
        boundary. Accepts device stacks or host ndarrays."""
        host = stack if isinstance(stack, np.ndarray) \
            else np.asarray(jax.device_get(stack))
        sh = NamedSharding(self.mesh,
                           P(self.axis, *([None] * (host.ndim - 1))))
        return jax.make_array_from_process_local_data(
            sh, host, (self.ndev,) + host.shape[1:])

    @staticmethod
    def _local_rows(garr, out: Dict[int, np.ndarray]) -> None:
        if garr.size == 0:  # zero-width lanes carry no bytes: jax keeps
            # them whole on every device instead of sharding them
            for g in range(garr.shape[0]):
                out[g] = np.zeros(garr.shape[1:], garr.dtype)
            return
        for s in garr.addressable_shards:
            g = s.index[0].start if isinstance(s.index[0], slice) \
                else int(s.index[0])
            out[int(g)] = np.asarray(s.data)[0]

    def _run_gang_epoch(self, blocks, schema, nparts: int, cap: int,
                        widths, char_caps, src_caps, results,
                        sid: int, epoch: int):
        ndev = self.ndev
        L = self._rt.local_devices
        fold = nparts != ndev
        spec = _lane_spec(schema)

        lane_meta, lane_datas, lane_valids = _lane_layout(spec)
        if fold:
            lane_meta.append((-1, (), "pid", None))
            lane_datas.append([])
            lane_valids.append([])

        pids_all, live_all = [], []
        char_stacks: Dict[tuple, tuple] = {}
        for slot in range(L):
            if slot < len(blocks):
                _, b, pids = blocks[slot]
                live = _pad1(b.live_mask(), cap)
                pids = _pad1(pids.astype(jnp.int32), cap)
            else:
                b = None
                pids = jnp.zeros((cap,), jnp.int32)
                live = jnp.zeros((cap,), jnp.bool_)
            pids_all.append(pids % ndev if fold else pids)
            live_all.append(live)
            _pack_block(b, schema, cap, widths, lane_datas, lane_valids,
                        spec, char_stacks=char_stacks)
            if fold:
                lane_datas[-1].append(pids)
                lane_valids[-1].append(live)

        host_stacks = [np.asarray(jax.device_get(jnp.stack(ls)))
                       for ls in lane_datas]
        datas = tuple(self._global(h) for h in host_stacks)
        valids = tuple(self._global(jnp.stack(ls))
                       for ls in lane_valids)
        pids_g = self._global(jnp.stack(pids_all))
        live_g = self._global(jnp.stack(live_all))
        sent = sum(h.nbytes for h in host_stacks)

        str_keys = [(ci, path) for ci, path, kind, _ in spec
                    if kind == "str_mat"]
        char_offs, char_bytes, cb_list = [], [], []
        for keyk in str_keys:
            # every member must even pack ABSENT string lanes (a member
            # whose epoch slots are all empty never touched char_stacks)
            offs_list, chars_list = char_stacks.get(
                keyk, ([jnp.zeros((cap + 1,), jnp.int32)] * L,
                       [jnp.zeros((0,), jnp.uint8)] * L))
            ch_cap = src_caps.get(keyk, 16)
            char_offs.append(self._global(jnp.stack(offs_list)))
            ch_host = np.asarray(jax.device_get(jnp.stack(
                [_pad1(c, ch_cap) for c in chars_list])))
            char_bytes.append(self._global(ch_host))
            cb_list.append(char_caps[keyk])
            sent += ch_host.nbytes

        self._check_cancel()
        out_datas, out_valids, out_live, out_rc, out_chars = \
            self._exchange(datas, valids, pids_g, live_g,
                           char_offs=char_offs, char_bytes=char_bytes,
                           char_caps=tuple(cb_list))
        MESH_COLLECTIVE_EPOCHS.inc()
        MESH_COLLECTIVE_BYTES.inc(sent)
        _FLIGHT.record("shuffle", ev="mesh_epoch", sid=int(sid),
                       epoch=int(epoch), bytes=int(sent),
                       nproc=self._rt.num_processes,
                       process=self._rt.process_id,
                       # owning query: the warehouse attributes gang-DCN
                       # bytes to the query that ran the collective
                       query=(self._qctx.query_id
                              if self._qctx is not None else ""))

        # readback through ADDRESSABLE shards only — a device_get of the
        # global arrays would span devices this process cannot address
        loc_datas: List[Dict[int, np.ndarray]] = \
            [{} for _ in lane_meta]
        loc_valids: List[Dict[int, np.ndarray]] = \
            [{} for _ in lane_meta]
        for li in range(len(lane_meta)):
            self._local_rows(out_datas[li], loc_datas[li])
            self._local_rows(out_valids[li], loc_valids[li])
        loc_live: Dict[int, np.ndarray] = {}
        loc_rc: Dict[int, np.ndarray] = {}
        self._local_rows(out_live, loc_live)
        self._local_rows(out_rc, loc_rc)
        payloads = {}
        si = 0
        for li, (ci, path, kind, _) in enumerate(spec):
            if kind == "str_mat":
                chunks: Dict[int, np.ndarray] = {}
                self._local_rows(out_chars[si], chunks)
                payloads[li] = (chunks, cb_list[si])
                si += 1

        len_lanes = _len_lane_indices(spec)
        for g in self._rt.owned_rows:
            if int(loc_rc[g]) == 0:
                continue
            live_d = jnp.asarray(loc_live[g])
            live_np = loc_live[g]
            flat_caps = {}
            for li in len_lanes:
                total = max(int(np.sum(np.where(
                    live_np, loc_datas[li][g], 0))), 1)
                if spec[li][2] == "str_len":
                    flat_caps[li - 1] = bucket_bytes(total, minimum=16)
                else:
                    flat_caps[li - 2] = bucket_rows(total)
            cols, pid_lane = _unpack_device(
                schema, lane_meta, loc_datas, loc_valids, g, live_d,
                flat_caps, payloads=payloads, ndev=ndev)
            landed = TpuBatch(cols, schema, ndev * cap,
                              selection=live_d)
            if not fold:
                results[g].append(landed)
            else:
                pid_j = jnp.asarray(pid_lane)
                for p in range(g, nparts, ndev):
                    results[p].append(
                        landed.with_selection(pid_j == p))
