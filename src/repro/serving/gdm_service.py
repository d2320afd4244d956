"""The real GDM chain behind the serving engine.

One :class:`GDMService` instance is one of the paper's S services: a DiT
denoiser (``repro.models.gdm``) whose chain the engine executes block by
block across nodes.  Two contracts back the engine:

* **execution** — ``run_batch(states, block_idxs)`` advances every request
  scheduled on a node this quantum in ONE jitted
  :func:`repro.models.gdm.run_block_batched` call over the stacked latents
  (requests may sit at different chain depths; the batched kernel takes
  per-sample block indices).  ``batch_calls`` counts those device calls so
  tests can assert one call per (node, quantum).
* **quality Ω(k)** — measured from the model itself via
  :func:`repro.models.gdm.quality_per_block` (SSIM proxy of the block-k x0
  estimate vs the full-chain output, the paper's Fig. 1 protocol), made
  monotone by running max.  The same measured curve is what the simulator
  trains against (``EdgeSimulator(cfg, quality=...)``), closing the
  sim → serving loop: the placement policy is trained and deployed on ONE
  quality function.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels.ops import resolve_impl
from repro.models.gdm import (LATENT_CHANNELS, init_gdm, make_schedule,
                              quality_per_block, run_block_batched)
from repro.serving.tracing import phase


def default_gdm_impl(impl: Optional[str], cfg: ModelConfig) -> str:
    """Resolve the denoise kernel impl for a service.

    Precedence: explicit ``impl`` argument > ``REPRO_GDM_IMPL`` env knob >
    ``ModelConfig.gdm_impl`` (default ``"auto"``).  ``"auto"`` picks Pallas
    on TPU and the XLA oracle elsewhere (``repro.kernels.ops.resolve_impl``)
    — serving no longer hardcodes ``"xla"``.
    """
    if impl:
        return impl
    env = os.environ.get("REPRO_GDM_IMPL", "").strip()
    if env:
        return env
    return getattr(cfg, "gdm_impl", "auto") or "auto"


def block_runner(cfg: ModelConfig, schedule, *, steps_per_block: int,
                 total_steps: int, impl: str, mesh=None,
                 batch_axis: str = "batch"):
    """The jitted stacked-block call of a service:
    ``(params, latent, prompt, block_idx) -> (latent, x0)``.

    The params are an argument: jit embeds closed-over arrays in the
    program as constants, one copy per compiled bucket.  With ``mesh`` each
    device runs the call on its own rows of the batch (the DiT is
    per-sample independent) under ``shard_map``: GSPMD cannot partition
    Pallas kernels, and their out_shapes carry no vma for the checker.
    """
    def run(params, latent, prompt, block_idx):
        with jax.named_scope("gdm_block"):
            return run_block_batched(params, latent, prompt, cfg, schedule,
                                     block_idx,
                                     steps_per_block=steps_per_block,
                                     total_steps=total_steps, impl=impl)

    jit_kw = {}
    if jax.default_backend() in ("gpu", "tpu"):
        # donate the stacked latent: the block call overwrites it anyway
        # (no-op on CPU, where donation only warns)
        jit_kw["donate_argnums"] = (1,)
    if mesh is not None:
        from repro.distributed.sharding import batch_shardings
        row = jax.P(batch_axis)
        run = jax.shard_map(run, mesh=mesh, in_specs=(jax.P(), row, row, row),
                            out_specs=(row, row), check_vma=False)
        data, replicated = batch_shardings(mesh, batch_axis)
        jit_kw["in_shardings"] = (replicated, data, data, data)
        jit_kw["out_shardings"] = (data, data)
    return jax.jit(run, **jit_kw)


class GDMService:
    """One GDM denoising-chain service (real reduced DiT) for the engine."""

    def __init__(self, key, *, num_blocks: int = 4, steps_per_block: int = 1,
                 model_cfg: Optional[ModelConfig] = None, prompt_len: int = 8,
                 ref_prompts: int = 4, mesh=None, batch_axis: str = "batch",
                 impl: Optional[str] = None):
        self.cfg = model_cfg or get_config("gdm-dit").reduced()
        self.num_blocks = num_blocks
        self.steps_per_block = steps_per_block
        self.prompt_len = prompt_len
        self.impl = default_gdm_impl(impl, self.cfg)
        self.resolved_impl = resolve_impl(self.impl)
        total = num_blocks * steps_per_block
        k_init, k_ref = jax.random.split(key)
        self.params = init_gdm(k_init, self.cfg)
        self.schedule = make_schedule(total)
        self.batch_calls = 0                       # device batch-call counter
        # one mesh shards the stacked batch dim across devices (the DiT is
        # per-sample independent: pure data parallelism, zero communication)
        self.mesh = mesh
        self._ndev = 1 if mesh is None else mesh.shape[batch_axis]
        # persistent per-bucket host staging buffers (see run_batch)
        self._buffers: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] \
            = {}
        self._slot_batch: Optional["SlotBatch"] = None
        self._runner = block_runner(self.cfg, self.schedule,
                                    steps_per_block=steps_per_block,
                                    total_steps=total, impl=self.impl,
                                    mesh=mesh, batch_axis=batch_axis)
        # observability (repro.serving.tracing): instrument() attaches a
        # MetricsRegistry; the block calls then time their phases into it,
        # and _call_runner flags compile events by first-seen (impl, bucket)
        # shape key (XLA recompiles are shape-keyed).  None -> untimed.
        self.metrics = None
        self.service_id = -1
        self._compiled_keys: set = set()

        # Ω(k): measured SSIM-vs-final per block (Fig. 1 protocol), forced
        # monotone — measured curves are monotone in expectation only
        prompts = jax.random.randint(k_ref, (ref_prompts, prompt_len), 2,
                                     self.cfg.vocab_size)
        q = np.asarray(quality_per_block(self.params, k_ref, prompts,
                                         self.cfg, num_blocks=num_blocks,
                                         steps_per_block=steps_per_block,
                                         impl=self.impl))
        self.omega = np.zeros(num_blocks + 1)
        self.omega[1:] = np.maximum.accumulate(np.clip(q, 0.0, 1.0))
        if mesh is not None:
            # replicate the weights once, after Ω (measured unsharded), so
            # the sharded block calls never re-transfer them
            from repro.distributed.sharding import batch_shardings
            _, replicated = batch_shardings(mesh, batch_axis)
            self.params = jax.device_put(self.params, replicated)

    def instrument(self, metrics, service: int) -> None:
        """Attach a :class:`repro.serving.tracing.MetricsRegistry` for the
        service with id ``service``.  Every non-empty block call (``run_batch``
        and :meth:`SlotBatch.step`) then times its phases ``stage_in``,
        ``launch``, ``device_wait`` and ``readback`` into it
        (:func:`repro.serving.tracing.phase`), tagged with the service, live
        rows and bucket.  A first call at a new (impl, bucket) shape is a
        compile event: counted in ``gdm_compile_events`` and timed to its
        end in ``gdm_compile_ms``.  Attach BEFORE serving traffic so the
        first-seen set is honest."""
        self.metrics = metrics
        self.service_id = int(service)

    def _call_runner(self, latent_buf, prompt_buf, idx_buf):
        """The one seam both batch paths (run_batch / SlotBatch.step) issue
        their device call through; uninstrumented it IS the raw call."""
        m = self.metrics
        if m is None:
            return self._runner(self.params, latent_buf, prompt_buf, idx_buf)
        m.counter("gdm_runner_calls").inc()
        key = (self.impl, int(latent_buf.shape[0]))
        if key in self._compiled_keys:
            return self._runner(self.params, latent_buf, prompt_buf, idx_buf)
        t0 = time.perf_counter()
        out = self._runner(self.params, latent_buf, prompt_buf, idx_buf)
        jax.block_until_ready(out)
        self._compiled_keys.add(key)
        m.counter("gdm_compile_events").inc()
        m.histogram("gdm_compile_ms").observe((time.perf_counter() - t0) * 1e3)
        return out

    def _wait(self, out, meta: dict) -> None:
        """With a registry attached: the ``device_wait`` phase, the host
        blocked on the block program.  Untraced, the read-back's
        ``np.asarray`` stays the only sync."""
        if self.metrics is not None:
            with phase(self.metrics, "device_wait", **meta):
                jax.block_until_ready(out)

    # -- engine contracts -----------------------------------------------------

    def _bucket(self, b: int) -> int:
        """Batch-size bucket for ``b`` live rows: pow2 up to 8, then
        multiples of 8 — bounded compile count with at most 7 wasted rows
        on the big fleet-stacked batches (pow2 alone wastes up to ~2x
        compute there); rounded up so the mesh batch axis always divides."""
        assert b > 0
        bucket = (1 << (b - 1).bit_length()) if b <= 8 else -(-b // 8) * 8
        if bucket % self._ndev:
            bucket = -(-bucket // self._ndev) * self._ndev
        return bucket

    def slot_batch(self) -> "SlotBatch":
        """The slot-resident batch view for the iteration-level scheduler
        (one per service, lazily built) — see :class:`SlotBatch`."""
        sb = getattr(self, "_slot_batch", None)
        if sb is None:
            sb = self._slot_batch = SlotBatch(self)
        return sb

    def init_state(self, rng: np.random.Generator) -> Dict:
        """Fresh request payload: noise latent + prompt token ids."""
        prompt = np.asarray(rng.integers(2, self.cfg.vocab_size,
                                         size=(self.prompt_len,)), np.int32)
        latent = np.asarray(
            rng.standard_normal((self.cfg.latent_hw ** 2, LATENT_CHANNELS)),
            np.float32)
        return {"latent": latent, "prompt": prompt, "x0": None}

    def run_batch(self, states: List[Dict],
                  block_idxs: np.ndarray) -> Tuple[List[Dict], np.ndarray]:
        """ONE jitted call for the whole (node, quantum) group.

        The batch is padded to the next power of two before the device call:
        serving batch sizes vary per quantum (and fleet-stacked batches vary
        more), so without bucketing every new size would trigger an XLA
        recompile.  The DiT is per-sample independent — padding rows never
        change the live rows' results; the pad is sliced off before the
        states are written back.  With a mesh, buckets round up to a
        multiple of the mesh size so the batch dim always divides.

        Rows are written into persistent per-bucket staging buffers (zeroed
        once per bucket size) instead of re-``np.stack``-ing fresh arrays
        every quantum — at fleet scale the per-call host allocations were a
        measurable slice of the stacked path's step time.
        """
        b = len(states)
        if b == 0:
            # empty-batch edge (ISSUE 9): a continuous-scheduler step where
            # every sample vacated must not issue a compiled call on a pad
            # row or bump batch_calls
            return [], self.omega[np.asarray(block_idxs, dtype=int) + 1]
        bucket = self._bucket(b)
        m = self.metrics
        meta = {"service": self.service_id, "rows": b, "bucket": bucket}
        with phase(m, "stage_in", **meta):
            buf = self._buffers.get(bucket)
            if buf is None:
                hw2 = self.cfg.latent_hw ** 2
                buf = self._buffers[bucket] = (
                    np.zeros((bucket, hw2, LATENT_CHANNELS), np.float32),
                    np.zeros((bucket, self.prompt_len), np.int32),
                    np.zeros((bucket,), np.int32))
            latent_buf, prompt_buf, idx_buf = buf
            for i, s in enumerate(states):
                latent_buf[i] = s["latent"]
                prompt_buf[i] = s["prompt"]
            idx_buf[:b] = np.asarray(block_idxs, np.int32)
            idx_buf[b:] = 0
        # pad rows keep whatever latents a previous call staged (plus a
        # valid block 0 index) — per-sample independence makes them inert
        with phase(m, "launch", **meta):
            result = self._call_runner(latent_buf, prompt_buf, idx_buf)
        self.batch_calls += 1
        self._wait(result, meta)
        with phase(m, "readback", **meta):
            latent, x0 = (np.asarray(a) for a in result)
            out = [dict(s, latent=latent[i], x0=x0[i])
                   for i, s in enumerate(states)]
            return out, self.omega[np.asarray(block_idxs) + 1]

    def block_fn(self, state: Dict, block_idx: int) -> Tuple[Dict, float]:
        """Scalar fallback (legacy per-request path): batch of one."""
        states, qs = self.run_batch([state], np.asarray([block_idx]))
        return states[0], float(qs[0])


class SlotBatch:
    """Slot-level batch mutation for the iteration-level scheduler.

    ``run_batch`` restages every row on every call — right for the quantum
    engine (one call per quantum), wasteful for the continuous scheduler,
    which calls the service every *block step* with mostly the SAME
    requests: under join/leave only the requests that joined or left since
    the previous step change.  A :class:`SlotBatch` keeps requests
    *resident* in persistent per-bucket staging buffers keyed by rid: a
    continuing request's latent row is already staged (the previous step's
    output was written back into its slot), so each step only writes the
    rows that joined and frees the rows that left.

    Correctness guards:

    * **Residency check by identity** — a row is trusted only if the
      request's current ``state["latent"]`` *is* the exact array this batch
      returned for that rid last step; anything else (a recycled rid from
      another run, a state mutated elsewhere, a fresh latent) restages the
      row.  Handover keeps the state object, so residency survives
      cross-cell moves (service instances are fleet-shared).
    * **Masked write-back** — outputs are copied back only into the rows
      planned THIS step; a resident-but-unplanned row keeps its staged
      latent (the device call computes pad rows too, but per-sample
      independence makes them inert and the write-back discards them).
    * **Own buffers** — the resident buffers are separate from
      ``run_batch``'s staging buffers (an interleaved ``run_batch`` call
      would silently overwrite resident rows), but both share the service's
      jitted runner and bucket sizes, so no new XLA compiles.

    Bucket churn compacts: when the bucket for the live count changes,
    every request restages into the new bucket's buffers (values are
    identical to the rows it held — the write-back keeps staged rows equal
    to the returned states).
    """

    def __init__(self, svc: GDMService):
        self.svc = svc
        self.bucket = 0
        self.rows: Dict[int, int] = {}             # rid -> resident row
        self._free: List[int] = []
        self._latent_of: Dict[int, np.ndarray] = {}   # rid -> returned view
        self._buffers: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] \
            = {}
        self.device_calls = 0
        self.rows_staged = 0                       # rows written (joins etc.)

    def _buffers_for(self, bucket: int):
        buf = self._buffers.get(bucket)
        if buf is None:
            hw2 = self.svc.cfg.latent_hw ** 2
            buf = self._buffers[bucket] = (
                np.zeros((bucket, hw2, LATENT_CHANNELS), np.float32),
                np.zeros((bucket, self.svc.prompt_len), np.int32),
                np.zeros((bucket,), np.int32))
        return buf

    def step(self, items: List[Tuple[int, Dict, int]]
             ) -> Tuple[List[Dict], np.ndarray]:
        """Advance one block step: ``items`` is ``[(rid, state, block_idx)]``
        for every request planned this step.  Returns ``(states,
        qualities)`` exactly like :meth:`GDMService.run_batch` (and
        bit-identical to it — pinned by ``tests/test_scheduler.py``)."""
        svc = self.svc
        if not items:
            return [], svc.omega[np.asarray([], dtype=int) + 1]
        bucket = svc._bucket(len(items))
        m = svc.metrics
        meta = {"service": svc.service_id, "rows": len(items),
                "bucket": bucket}
        with phase(m, "stage_in", **meta):
            if bucket != self.bucket:
                # bucket churn: compact into the new bucket's buffers
                # (every row restages below via the residency check)
                self.bucket = bucket
                self.rows = {}
                self._free = []
                self._latent_of = {}
            latent_buf, prompt_buf, idx_buf = self._buffers_for(bucket)
            # leaves: free the rows of rids not planned this step (a
            # request skipping a step loses residency and restages when it
            # returns)
            planned = {rid for rid, _, _ in items}
            for rid in [r for r in self.rows if r not in planned]:
                self._free.append(self.rows.pop(rid))
                self._latent_of.pop(rid, None)
            self._free.sort(reverse=True)          # reuse lowest rows first
            # joins (and residency-check failures): stage their rows
            next_row = len(self.rows) + len(self._free)
            for rid, state, _ in items:
                row = self.rows.get(rid)
                resident = row is not None and \
                    state["latent"] is self._latent_of.get(rid)
                if row is None:
                    if self._free:
                        row = self._free.pop()
                    else:
                        row = next_row
                        next_row += 1
                    self.rows[rid] = row
                if not resident:
                    latent_buf[row] = state["latent"]
                    prompt_buf[row] = state["prompt"]
                    self.rows_staged += 1
            idx_buf[:] = 0                         # pad rows: valid block 0
            for (rid, _, k) in items:
                idx_buf[self.rows[rid]] = k
        with phase(m, "launch", **meta):
            result = svc._call_runner(latent_buf, prompt_buf, idx_buf)
        svc.batch_calls += 1
        self.device_calls += 1
        svc._wait(result, meta)
        with phase(m, "readback", **meta):
            latent_out, x0 = (np.asarray(a) for a in result)
            out: List[Dict] = []
            for rid, state, _ in items:
                row = self.rows[rid]
                # masked write-back: only planned rows advance in the
                # staging buffer; the returned view is the residency token
                # for next step
                latent_buf[row] = latent_out[row]
                self._latent_of[rid] = latent_row = latent_out[row]
                out.append(dict(state, latent=latent_row, x0=x0[row]))
            ks = np.asarray([k for _, _, k in items], dtype=int)
            return out, svc.omega[ks + 1]


def make_gdm_services(num_services: int, key, *, num_blocks: int = 4,
                      steps_per_block: int = 1,
                      model_cfg: Optional[ModelConfig] = None,
                      mesh=None, batch_axis: str = "batch",
                      impl: Optional[str] = None,
                      ) -> Tuple[Dict[int, GDMService], np.ndarray]:
    """One independent DiT per service + the stacked (S, B+1) Ω matrix.

    The Ω matrix is what the sim trains on (``EdgeSimulator(cfg,
    quality=omega)``) and what the engine delivers against — the single
    source of quality truth for the closed loop.
    """
    services = {}
    for s, k in enumerate(jax.random.split(key, num_services)):
        services[s] = GDMService(k, num_blocks=num_blocks,
                                 steps_per_block=steps_per_block,
                                 model_cfg=model_cfg, mesh=mesh,
                                 batch_axis=batch_axis, impl=impl)
    omega = np.stack([services[s].omega for s in range(num_services)])
    return services, omega
