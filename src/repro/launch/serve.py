"""Multi-tenant serving driver: CaMDN as a first-class runtime feature.

Co-locates several models on one device pool.  Each tenant's FFN block
is described as a small :class:`~repro.core.types.ModelGraph` and mapped
by the *same* offline machinery the simulator uses
(:class:`~repro.core.runtime.TenantModel` -> per-layer MCTs with LWM
candidates at every usage limit + the fused-block LBM candidate), and
the per-epoch scheduling runs the same
:class:`~repro.core.runtime.TenantTask` state machine under a
:class:`~repro.core.policy.CamdnPolicy` — the serving loop and the
simulator share one CachePolicy runtime:

  pages granted -> candidate (LBM fused kernel vs LWM tiles) -> decode.

The execution side is pipelined around **scheduling epochs**:

* **Epoch-granted scan decode.**  A CaMDN grant is held for a window of
  ``epoch_len`` decode steps, and the window executes as ONE on-device
  ``jax.lax.scan`` over the static KernelPlan
  (:func:`repro.models.transformer.decode_epoch`), amortizing jit
  dispatch and Python scheduling from per-token to per-epoch.  The KV /
  SSM caches are donated (``donate_argnums``), so XLA updates them in
  place across the epoch.  The block's NEC traffic is charged once with
  ``repeat=K`` (:attr:`TenantTask.charge_repeat`) — bit-identical
  counters to charging every step.
* **Plan-bucketed batching.**  Tenants sharing an (arch, KernelPlan)
  pair stack along a leading tenant axis and decode as one vmapped
  device call — one compile-cache entry and one dispatch serve the
  whole bucket.
* **One-epoch-ahead host/device overlap.**  The whole epoch launches as
  ONE fused jit call (every tenant's epoch scan an independent subgraph
  of a single XLA computation), and CaMDN selection, NEC charging, and
  plan lowering for epoch s+1 run while epoch s is still executing on
  device: JAX dispatch is asynchronous and the loop never pulls a
  device value — tokens and caches stay on device, and results are
  fetched once after the last epoch.

The server is a **continuous-batching** server: tenants may arrive
mid-run with real prompts (:class:`~repro.sim.driver.TenantSpec` /
:class:`~repro.sim.driver.PoissonArrivals` — the same arrival vocabulary
the analytic simulator uses), and each prompt is consumed as a sequence
of **cache-aware prefill chunks** interleaved into the epoch pipeline:

* An arriving tenant reserves pages for its KV working set (held until
  departure — the long-lived VMEM occupant a prompt brings), then its
  prompt is prefilled chunk by chunk.  Each chunk is scheduled as a
  first-class work item inside the epoch: the tenant's prefill-block
  MCT runs through ``policy.charge_and_plan`` (NEC-charged per chunk),
  the granted Selection lowers through the existing KernelPlan
  machinery, and the *chunk length* is lowered from that grant
  (:func:`repro.core.plan.lower_prefill_chunk`) — a big grant prefills
  in large chunks, a starved grant degrades to one-LANE chunks instead
  of thrashing the shared pool.  Grants are renegotiated between
  chunks, so the allocator's dynamic algorithm visibly resizes chunk
  shapes as co-located tenants come and go.
* Chunks write KV into the live cache prefix via the existing
  LANE-aligned ``kv_len`` windows
  (:func:`repro.models.transformer.prefill_chunk`); after the last
  chunk the tenant flips to decode with no recompile of its bucket.
  Chunk execution follows the reference jnp path, so any chunking of a
  prompt is bit-identical to a one-shot prefill — which is what makes
  decode outputs bit-identical between the two admission modes below.
* ``admission="interleaved"`` (continuous batching) plans prefill
  chunks and decode windows as work items of the SAME scheduling epoch:
  the chunks dispatch through small per-arch chunk programs (cached
  across epochs and across same-arch arrivals — folding their
  run-to-run-varying shapes into the fused epoch jit would recompile
  the whole epoch per chunk resize) back-to-back with the fused decode
  call, all asynchronously, so decode never stalls on admission.
  ``admission="sequential"`` is the static-batching baseline the
  serving benchmark measures against: a request waits for the in-flight
  batch to DRAIN before it is admitted (the queue wait counts against
  its TTFT), then its whole prompt prefills as one exclusive
  synchronous call, FCFS, before decode resumes.  Per-tenant
  time-to-first-token (TTFT) is recorded either way.

``pipeline=False`` keeps the serial reference loop (one scheduled,
charged, dispatched step per token); its outputs are bit-identical to
the pipelined loop and it is the baseline the serving benchmark
(``benchmarks/run.py`` -> ``BENCH_serve.json``) measures speedup
against.

A tenant spec that names an arch id serves that arch's reduced CPU
variant; a spec carrying an ArchConfig serves it exactly as given
(:func:`resolve_arch`).  The Pallas kernels run compiled on a TPU
backend and in interpret mode elsewhere (kernels/ops.py).  The allocation
trace (who held how many pages, which candidates ran, bypass decisions)
is the serving-side reproduction of the paper's runtime behaviour.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import math
import os
import shutil
import tempfile
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.allocator import AHEAD_FRACTION, INF, Selection
from repro.core.cache import CacheConfig
from repro.core.mapping import MapperConfig
from repro.core.mct import MCT, ModelMapping
from repro.core.plan import KernelPlan, lower_prefill_chunk
from repro.checkpoint import checkpoint as ckpt
from repro.core.policy import (KV_PRECISION_LADDER, CamdnPolicy,
                               QosPreemptionPolicy, ReplicaAllocators,
                               ReplicaControl, choose_kv_dtype,
                               price_layer_batch, project_epoch_dram)
from repro.core.runtime import (STATE_ADMITTED, STATE_PREEMPTED,
                                STATE_RESUMED, STATE_RUNNING, STATE_SHED,
                                TenantModel, TenantTask)
from repro.core.types import GemmDims, LayerKind, LayerSpec, ModelGraph, \
    ceil_div, elem_bytes
from repro.core.vmem import (LANE, PAGE_BYTES, VMEM_PAGES, fused_ffn_pages,
                             kv_row_bytes, lower_selection)
from repro.distributed import sharding as shard
from repro.distributed.fault_tolerance import StragglerPolicy
from repro.launch.spans import (ADMIT, DISPATCH, FINISH, FIRST_TOKEN, PLAN,
                                SpanRecorder, now as clock)
from repro.models import model as M
from repro.models.base import ArchConfig, get_arch
from repro.models.ssm import CONV_K
from repro.models.transformer import (init_caches, num_groups,
                                      seed_caches_from_prefix,
                                      ssm_state_in_place)
from repro.sim.driver import (BackoffPolicy, FleetScenario, PoissonArrivals,
                              TenantSpec)
from repro.sim.faults import FaultEvent, FaultLog, FaultPlan


def _elem_bytes(cfg: ArchConfig) -> int:
    """Activation/weight element size for the VMEM working-set math.
    Delegates to :func:`repro.core.types.elem_bytes`, which raises on an
    unknown dtype string — the old local table silently defaulted to 4,
    so a typo'd cfg.dtype inflated every working-set quote unnoticed."""
    return elem_bytes(cfg.dtype)


def resolve_arch(model: Union[str, ArchConfig]) -> ArchConfig:
    """The config a tenant spec's ``model`` names.  An ArchConfig is
    served exactly as given (published widths, depth cut by
    :meth:`~repro.models.base.ArchConfig.depth_cut`); a registered arch
    id resolves to its reduced CPU variant."""
    if isinstance(model, ArchConfig):
        return model
    return get_arch(model).reduced()


def _ffn_width(cfg: ArchConfig) -> int:
    """d_ff of the layer's schedulable GEMM block.  Archs without a dense
    FFN (SSM, d_ff 0) schedule their projections as a d_model-wide
    block, so the LBM quote and the plan lowering never see d_ff 0."""
    return max(cfg.d_ff, cfg.d_model)


def _ffn_graph(name: str, cfg: ArchConfig, seq_block: int) -> ModelGraph:
    """One transformer layer's FFN as a schedulable layer graph
    (gate/up -> down), so the core mapper derives its MCTs — LWM tile
    candidates per usage limit plus the fused-block LBM candidate —
    instead of serve.py hand-building them.  ``seq_block`` is padded to
    the 128-lane MXU tile: the Pallas kernels compute on padded tiles,
    so the schedulable VMEM working set is the padded one."""
    eb = _elem_bytes(cfg)
    seq_block = max(seq_block, LANE)
    d, f = cfg.d_model, _ffn_width(cfg)
    up = LayerSpec(
        "ffn.up", LayerKind.GEMM,
        (GemmDims(M=seq_block, N=f, K=d, reps=2, b_reused=False),),  # gate+up
        input_bytes=seq_block * d * eb, output_bytes=seq_block * f * eb,
        weight_bytes=2 * d * f * eb, elem_bytes=eb)
    down = LayerSpec(
        "ffn.down", LayerKind.GEMM,
        (GemmDims(M=seq_block, N=d, K=f),),
        input_bytes=seq_block * f * eb, output_bytes=seq_block * d * eb,
        weight_bytes=f * d * eb, elem_bytes=eb)
    return ModelGraph(f"{name}.ffn", [up, down])


def _vmem_mapper(total_pages: int) -> MapperConfig:
    """MapperConfig solving against the VMEM page pool instead of the
    SoC shared cache: same mapper, different substrate."""
    return MapperConfig(page_bytes=PAGE_BYTES,
                        npu_subspace_bytes=total_pages * PAGE_BYTES)


def _kv_reserve_pages(cfg: ArchConfig, batch: int, tokens: int,
                      kv_dtype: str = "native") -> int:
    """Pages an admitted prompt-tenant reserves for its KV / state
    working set — the long-lived VMEM occupant a real prompt brings
    (the decode cache prefix its chunks fill).  Attention archs scale
    with the prompt; SSM state is O(1); hybrids carry both.  This is
    what makes the serving-side dynamic allocation visible: reserved
    pages squeeze co-tenants' grants (and chunk sizes) and are returned
    on departure.  ``kv_dtype`` prices the KV rows at the tenant's
    chosen storage precision (plus the per-row fp32 scale stripes a
    quantized cache carries) — precision-for-residency: the int8 quote
    is what lets a starved tenant's reservation fit the pool."""
    eb = _elem_bytes(cfg)
    quantized = kv_dtype != "native"
    kv_eb = elem_bytes(kv_dtype) if quantized else eb
    G = num_groups(cfg)
    kv_groups = G if cfg.family != "ssm" else 0
    ssm_groups = {"ssm": G, "hybrid": G * (cfg.attn_every - 1)}.get(
        cfg.family, 0)
    row = kv_row_bytes(cfg.num_kv_heads, cfg.hd, kv_eb, scaled=quantized)
    kv = kv_groups * batch * tokens * row
    state = ssm_groups * batch * (
        (CONV_K - 1) * (cfg.d_inner + 2 * cfg.ssm_state) * eb
        + cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4)
    return ceil_div(kv + state, PAGE_BYTES) if tokens > 0 else 0


# Session-replay prompts draw from fixed-cap PRNG streams and slice:
# jax.random.randint output depends on the requested shape, so slicing
# one capped array is what makes turn t+1's prompt EXTEND turn t's
# bit-exactly (and every session on a system prompt share its prefix).
_PROMPT_CAP = 4096


def _prompt_tokens(spec: TenantSpec, i: int, cfg: ArchConfig,
                   batch: int) -> np.ndarray:
    """Deterministic prompt tokens for an admitted spec.

    Legacy specs (``prompt_seed`` unset) keep the exact seed behaviour:
    one admission-indexed stream shaped by the prompt length.  Session
    specs compose a shared system-prompt prefix (keyed by
    ``prefix_seed``) with a per-session suffix (keyed by
    ``prompt_seed``), both sliced from fixed-cap streams — the content
    identities cross-tenant KV dedup hashes."""
    P = spec.prompt_len
    if spec.prompt_seed is None:
        return np.asarray(jax.random.randint(
            jax.random.PRNGKey(7919 + i), (batch, P), 0, cfg.vocab_size),
            np.int32)
    pre_len = min(spec.prefix_len, P)
    assert P <= _PROMPT_CAP, f"prompt_len {P} > cap {_PROMPT_CAP}"
    pre = np.asarray(jax.random.randint(
        jax.random.PRNGKey(104729 + spec.prefix_seed),
        (batch, _PROMPT_CAP), 0, cfg.vocab_size), np.int32)[:, :pre_len]
    suf = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7919 + spec.prompt_seed),
        (batch, _PROMPT_CAP), 0, cfg.vocab_size), np.int32)[:, :P - pre_len]
    return np.ascontiguousarray(np.concatenate([pre, suf], axis=1))


def _prefix_candidates(prompt: np.ndarray, prompt_len: int,
                       align: int) -> List[Tuple[int, bytes]]:
    """(kv_len, token_bytes) probe list for the PrefixIndex, longest
    first: the full prompt, then every chunk-grid multiple below it."""
    lens = [prompt_len]
    lens += list(range((prompt_len - 1) // align * align, 0, -align))
    return [(l, prompt[:, :l].tobytes()) for l in lens]


def _params_key(spec: TenantSpec, kv_dtype: str) -> str:
    """Prefix-index params identity: the param seed, suffixed with the
    KV storage precision when quantized.  A quantized cache snapshot is
    only bit-valid for a tenant decoding at the same precision — the
    suffix keeps mixed-precision tenants sharing one param seed from
    attaching to each other's entries."""
    key = f"ps{spec.param_seed}"
    if kv_dtype != "native":
        key += f"+kv:{kv_dtype}"
    return key


class _LruCache:
    """Bounded LRU map for the server's jit caches: under churning tenant
    mixes the (plans, k, kv) key space grows without bound, so the
    coldest program is evicted past ``capacity``.  Hit/miss counters
    double as the server's compile counter — every miss on a jit cache
    corresponds to one program build (and one XLA compile at its first
    call).  Thread-safe: the AOT precompile thread populates these maps
    concurrently with the dispatch loop."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._d: "OrderedDict" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, default=None):
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                self.hits += 1
                return self._d[key]
            self.misses += 1
            return default

    def peek(self, key, default=None):
        """Counter-free lookup (no hit/miss accounting, no LRU touch)."""
        with self._lock:
            return self._d.get(key, default)

    def __setitem__(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)
                self.evictions += 1

    def setdefault(self, key, value):
        with self._lock:
            if key in self._d:
                self._d.move_to_end(key)
                return self._d[key]
            self._d[key] = value
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)
                self.evictions += 1
            return value

    def pop(self, key, default=None):
        with self._lock:
            return self._d.pop(key, default)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._d

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def keys(self):
        with self._lock:
            return list(self._d.keys())


def _aval_sig(args) -> Tuple:
    """Structural signature of a pytree of arrays / ShapeDtypeStructs:
    what the AOT precompiler keys its aval-specialized executables on.
    Computed identically for abstract specs at compile time and concrete
    device arrays at dispatch time."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    # .shape/.dtype attribute reads only — this runs per fused dispatch,
    # and dtype promotion (jnp.result_type) or str() per leaf is measurable
    # against a millisecond epoch
    return (treedef, tuple((tuple(x.shape), x.dtype) for x in leaves))


class _CompiledEntry:
    """One fused-epoch program: the lazily-compiling ``jax.jit`` wrapper
    plus any AOT-precompiled aval-specialized executables
    (``jit(...).lower(specs).compile()``) the background warmup produced.
    Dispatch prefers the matching precompiled executable (steady state:
    zero compiles on the epoch boundary) and falls back to the jit
    wrapper on any signature mismatch."""

    __slots__ = ("fallback", "aot", "aot_hits", "fallback_calls")

    def __init__(self, fallback):
        self.fallback = fallback
        self.aot: Dict[Tuple, Any] = {}
        self.aot_hits = 0
        self.fallback_calls = 0

    def __call__(self, *args):
        if self.aot:
            compiled = self.aot.get(_aval_sig(args))
            if compiled is not None:
                try:
                    out = compiled(*args)
                    self.aot_hits += 1
                    return out
                except (TypeError, ValueError):
                    pass   # aval/weak-type drift: recompile lazily below
        self.fallback_calls += 1
        return self.fallback(*args)


@dataclasses.dataclass
class Tenant:
    tid: str
    cfg: ArchConfig
    params: Any
    caches: Any
    decode: Any        # one-step jit (serial reference path)
    task: TenantTask
    token: Any         # [B, 1] int32 device array: next input (feedback);
    #                    None until a prompt tenant finishes prefill
    enc: Any = None    # encdec: fixed encoder output, built once
    index: int = 0
    tokens_served: int = 0
    epochs_served: int = 0
    choices: List[str] = dataclasses.field(default_factory=list)
    plans: List[KernelPlan] = dataclasses.field(default_factory=list)
    # decoded tokens, one [B, k] device array per epoch — fetched to the
    # host only once, after the serving loop finishes
    outputs: List[Any] = dataclasses.field(default_factory=list)
    # ---- continuous batching ----------------------------------------
    prompt: Optional[np.ndarray] = None   # [B, P] int32 host tokens
    prompt_len: int = 0
    pf_pos: int = 0                       # prompt tokens already in cache
    ptask: Optional[TenantTask] = None    # prefill-side task (chunk MCT)
    chunks: List[int] = dataclasses.field(default_factory=list)
    budget_left: Optional[int] = None     # decode steps before departure
    departed: bool = False
    # QoS target (seconds/token), resolved ONCE at admission by
    # most-specific match over the server's qos_targets patterns —
    # _slack must not re-run the pattern match every epoch
    qos_target: Optional[float] = None
    admitted_wall: Optional[float] = None  # due stamp: TTFT clock start
    ttft: Optional[float] = None          # seconds due -> 1st token
    run_steps: int = 0                    # decode steps this run() call
    # ---- KV reservation accounting (best-effort degradation) --------
    kv_wanted: int = 0                    # pages the working set asks for
    kv_reserved: int = 0                  # pages actually reserved
    # ---- precision-for-residency ------------------------------------
    kv_dtype: str = "native"              # KV storage precision (plan axis)
    # ---- prefix-hash KV dedup ---------------------------------------
    pf_computed: int = 0                  # prompt tokens prefilled on-device
    prefix_hit: int = 0                   # prompt tokens attached from index
    prefix_key: Optional[str] = None      # attached entry (detach on depart)
    dedup: Optional[Tuple[str, str]] = None   # (arch, params_key) when
    #                                           eligible to register/attach
    # ---- fault tolerance (preempt / resume) -------------------------
    state: str = STATE_ADMITTED           # admission state machine
    preemptions: int = 0
    preempted_wall: Optional[float] = None
    resume_step: Optional[int] = None     # logical step to retry resume at
    recovery_s: List[float] = dataclasses.field(default_factory=list)
    ckpt_ref: Optional[Dict[str, Any]] = None   # snapshot handle while
    #                                             PREEMPTED (mode + locator)

    @property
    def prefilling(self) -> bool:
        return self.prompt is not None and self.pf_pos < self.prompt_len


class MultiTenantServer:
    """Decode across tenants with CaMDN VMEM arbitration.

    ``qos_targets`` (tenant-id suffix -> seconds/token) switches the
    round-robin to deadline-aware scheduling (paper Fig. 9 experiment,
    serving side): the tenant with the worst QoS slack is scheduled
    first, and its allocator request is tried before anyone else touches
    the page pool — CaMDN integrated with an AuRORA-style priority
    policy.

    ``epoch_len`` is K, the number of decode steps one grant covers;
    ``pipeline=False`` selects the serial reference loop (per-step
    scheduling, charging, and dispatch — the pre-pipeline behaviour).

    Continuous batching: ``tenants`` / ``arrivals`` add prompt-driven
    dynamic tenants (see module docstring).  ``arrive_at`` seconds map
    onto the server's logical step clock via ``steps_per_s`` (the clock
    advances ``epoch_len`` per pipelined epoch, 1 per serial round), so
    admission points are deterministic and identical across admission
    modes.  ``admission`` selects interleaved chunked prefill (default)
    or the sequential whole-prompt-then-decode baseline;
    ``prefill_chunk`` is the nominal (maximum) chunk length the chunk
    MCT is built for.
    """

    def __init__(self, arch_ids: Optional[List[str]] = None, batch: int = 2,
                 max_len: int = 128, total_pages: int = VMEM_PAGES,
                 qos_targets: Optional[Dict[str, float]] = None,
                 epoch_len: int = 8, pipeline: bool = True,
                 tenants: Optional[List[TenantSpec]] = None,
                 arrivals: Optional[PoissonArrivals] = None,
                 admission: str = "interleaved",
                 prefill_chunk: int = 2 * LANE,
                 steps_per_s: float = 1.0,
                 device: Any = None, replica: str = "",
                 control: Optional[ReplicaControl] = None,
                 prefix_dedup: bool = False,
                 kv_dtype: str = "native",
                 batch_sched: bool = True,
                 lookahead: bool = False,
                 aot_warmup: bool = False,
                 faults: Optional[FaultPlan] = None,
                 preemption_policy: Any = None,
                 straggler_policy: Optional[StragglerPolicy] = None,
                 ckpt_dir: Optional[str] = None,
                 queue_limit: Optional[int] = None,
                 queue_deadline_s: Optional[float] = None,
                 backoff: Optional[BackoffPolicy] = None):
        assert admission in ("interleaved", "sequential"), admission
        assert kv_dtype in KV_PRECISION_LADDER + ("auto",), kv_dtype
        self.qos_targets = qos_targets or {}
        self.prefix_dedup = bool(prefix_dedup)
        # KV storage precision policy: a fixed rung pins every prompt
        # tenant; "auto" walks the ladder per admission — the first
        # precision whose full reservation fits the pool's current free
        # pages wins, so a starved arrival trades precision for
        # residency instead of degrading to a partial reservation
        self.kv_dtype = kv_dtype
        # Host-off-the-critical-path knobs: batch_sched plans contiguous
        # decode runs through the batched Algorithm 1 (bit-identical to
        # the per-tenant oracle; False forces the oracle — the
        # differential-testing switch); lookahead enables predictive
        # grant adjustment against next-epoch contention (changes grants
        # by design, so opt-in); aot_warmup precompiles the reachable
        # fused epoch programs on a background thread at run start /
        # admission (single-device servers only)
        self.batch_sched = bool(batch_sched)
        self.lookahead = bool(lookahead)
        self.aot_warmup = bool(aot_warmup)
        self.epoch_len = max(1, int(epoch_len))
        self.pipeline = bool(pipeline)
        self.admission = admission
        self.prefill_block = max(LANE, int(prefill_chunk))
        self.steps_per_s = steps_per_s
        # Fleet placement: ``device`` pins every tenant's params /
        # caches / feedback token to one chip (jax.device_put commits
        # them, and committed inputs drive where each jit executes), or
        # carries a per-replica (1, tp) submesh for tensor-parallel
        # replica groups (params/caches device_put with the
        # distributed.sharding specs; shard_hint activates during
        # tracing via use_mesh).  None (the default) keeps the seed
        # single-device behaviour untouched.
        self.mesh = device if isinstance(device, Mesh) else None
        if self.mesh is not None and self.mesh.devices.size == 1:
            device, self.mesh = self.mesh.devices.flat[0], None
        self.device = device
        self.replica = replica
        # VMEM page pool modeled by the same SharedCache/allocator the
        # simulator uses — bundled as one per-replica ReplicaControl
        # stack (fleet replicas pass theirs in, keyed by replica id; a
        # standalone server builds a private one).  The whole pool is
        # CaMDN-schedulable VMEM (XLA's reserved slice is already
        # subtracted in core.vmem.VMEM_BYTES).
        self.control = control or ReplicaControl.build(
            replica or "solo", CacheConfig(
                total_bytes=total_pages * PAGE_BYTES,
                num_slices=1, num_ways=1, npu_ways=1,
                page_bytes=PAGE_BYTES))
        self.cache = self.control.cache
        self.nec = self.control.nec
        self.alloc = self.control.alloc
        self.policy = self.control.policy
        self.prefix = self.control.prefix
        total_pages = self.cache.config.num_pages
        self.mapper = _vmem_mapper(total_pages)
        self.tenants: List[Tenant] = []
        self.batch = batch
        self.max_len = max_len
        self._clock = 0               # logical step clock (admissions)
        self._n_admitted = 0
        # jitted one-step functions are shared per arch so same-arch
        # tenants hit one compile cache (the pipelined path compiles
        # through _fused_epoch_fn instead)
        self._step_fns: Dict[str, Any] = {}
        self._cfgs: Dict[str, ArchConfig] = {}
        self._groups: Dict[str, List[Tenant]] = {}
        self._batched: Dict[str, Any] = {}   # arch -> stacked params
        # un-jitted epoch / prefill cores per arch, composed into the
        # one fused per-epoch device call (_fused_epoch_fn); jitted per
        # distinct (work-item structure, plans, k, kv) combination
        self._epoch_cores: Dict[str, Any] = {}
        # bounded LRU jit caches: under churning tenant mixes the
        # (plans, k, kv) key space is unbounded, and each entry pins an
        # XLA executable — the coldest programs are evicted.  Capacities
        # comfortably cover the steady-state working set (asserted by
        # tests/test_host_overlap.py: the smoke workload's hit rate is
        # unchanged vs unbounded maps).
        self._batched_cores = _LruCache(capacity=8)
        self._fused_jits = _LruCache(capacity=64)
        self._prefill_jits = _LruCache(capacity=16)
        self._prefill_cores: Dict[str, Any] = {}
        # (arch, kv_dtype) -> prefix cache seeder
        self._seed_jits: Dict[Tuple[str, str], Any] = {}
        # ---- host-path instrumentation ------------------------------
        # spans (admit, plan, dispatch, first token, finish), per-task
        # stamps and per-epoch counters of the current run, on the one
        # clock every stamp of the server reads
        self.spans = SpanRecorder()
        self._lookahead_adjusted = 0
        self._batched_runs = 0
        self._oracle_runs = 0
        # ---- AOT plan-bucket precompile -----------------------------
        self._aot_threads: List[threading.Thread] = []
        self._aot_compiled = 0
        self._aot_failed = 0
        # per-site breakdown of AOT warmup failures (observability for
        # the swallowed-exception paths in warm_aot)
        self._aot_failed_enum = 0
        self._aot_failed_compile = 0
        self._run_steps = 0
        # ---- fault tolerance / overload admission -------------------
        # faults: a logical-clock fault schedule consumed at epoch
        # boundaries; None (the default) keeps the seed behaviour
        # untouched.  The straggler detector only arms when a plan is
        # installed — detection feeds on a *logical* per-epoch duration
        # stream (1.0 per clean epoch, x factor per injected straggler
        # epoch), so trips are deterministic on any host.
        self.faults = faults
        self.fault_log = FaultLog()
        self.preemption_policy = preemption_policy or QosPreemptionPolicy()
        self.straggler = straggler_policy or StragglerPolicy()
        self._straggler_left = 0
        self._straggler_factor = 1.0
        self._ckpt_dir = ckpt_dir
        self._owns_ckpt_dir = False
        self._pressure_holds: List[List] = []   # [release_step, holder]
        self._pressure_n = 0
        # overload admission control: a bounded arrival queue
        # (queue_limit) and a deadline-aware defer/degrade/shed ladder
        # (queue_deadline_s + jittered backoff).  Both default OFF —
        # admission then behaves exactly like the seed (immediate,
        # best-effort degrading).
        self.queue_limit = queue_limit
        self.queue_deadline_s = queue_deadline_s
        self.backoff = backoff or (BackoffPolicy()
                                   if queue_deadline_s is not None else None)
        self.shed: List[Dict[str, Any]] = []
        self.deferrals = 0
        self._defer_attempts: Dict[int, int] = {}
        # persistent tenant-stacked caches per bucketed arch group: the
        # stacked buffer stays stacked (and donated) across epochs while
        # the bucket holds, instead of an O(cache bytes) restack/slice
        # round-trip per epoch; it is unstacked back into the tenants
        # only when the bucket breaks or the run ends
        self._bucket_caches: Dict[str, Any] = {}
        # ---- admission queue ----------------------------------------
        specs: List[TenantSpec] = [TenantSpec(aid) for aid in arch_ids or []]
        specs += list(tenants or [])
        if arrivals is not None:
            specs += arrivals.specs()
        specs.sort(key=lambda s: s.arrive_at)
        # queue entries are [spec, due_wall, arrive_step]: due_wall is
        # stamped when the logical clock first passes arrive_step (the
        # request exists from then on), so a sequential-admission queue
        # wait counts against TTFT even though the tenant is admitted
        # later
        self._queue: List[List] = []
        for spec in specs:
            if spec.arrive_at <= 0.0:
                self._admit_spec(spec)
            else:
                self.enqueue([spec])

    def enqueue(self, specs: List[TenantSpec]) -> None:
        """Queue arrivals relative to the CURRENT logical clock (a
        benchmark warms the compile caches by replaying one scenario on
        the same server: arch/shape-keyed jit caches carry over, tenant
        state does not).  With a bounded queue (``queue_limit``),
        arrivals past capacity are shed on the spot — backpressure at
        the front door instead of unbounded buildup."""
        for spec in sorted(specs, key=lambda s: s.arrive_at):
            if (self.queue_limit is not None
                    and len(self._queue) >= self.queue_limit):
                self._shed(spec, None, reason="queue_full")
                continue
            step = self._clock + int(math.ceil(spec.arrive_at
                                               * self.steps_per_s))
            self._queue.append([spec, None, step])
        self._queue.sort(key=lambda it: it[2])

    # -------------------------------------------------- fleet feedback --
    def load(self) -> int:
        """Router load metric: pages granted out of this replica's pool
        (decode/prefill grants plus the long-lived KV reservations) plus
        the prefill chunks still queued — the feedback the fleet's
        least-loaded admission layer reads back from each replica's
        control stack every routing round."""
        used = self.cache.config.num_pages - self.cache.free_pages
        chunks = sum(ceil_div(t.prompt_len - t.pf_pos, self.prefill_block)
                     for t in self.tenants
                     if not t.departed and t.prefilling)
        return used + chunks

    def active_count(self) -> int:
        return sum(1 for t in self.tenants if not t.departed)

    def page_utilization(self) -> float:
        return self.control.utilization

    def admit_routed(self, spec: TenantSpec,
                     due_wall: Optional[float] = None) -> "Tenant":
        """Fleet admission: the global router hands a *due* spec
        straight to this replica, bypassing the local arrival queue —
        arrival timing is owned by the fleet's clock."""
        return self._admit_spec(spec, due_wall)

    # ------------------------------------------------------- placement --
    def _put(self, x: Any) -> Any:
        """Commit an array pytree to this replica's chip (identity on a
        plain single-device server).  Committed inputs are what make
        every one of this server's jit calls execute on its own chip —
        uncommitted operands (prompt slices, scalar indices) follow."""
        if self.device is None or x is None:
            return x
        return jax.device_put(x, self.device)

    def _put_params(self, params: Any) -> Any:
        if self.mesh is not None:
            return jax.device_put(params,
                                  shard.param_shardings(params, self.mesh))
        return self._put(params)

    def _put_caches(self, caches: Any) -> Any:
        if self.mesh is not None:
            return jax.device_put(
                caches, shard.cache_shardings(caches, self.mesh, self.batch))
        return self._put(caches)

    def _put_replicated(self, x: Any) -> Any:
        """Tokens / encoder outputs on a tensor-parallel replica group:
        replicated across the group's chips."""
        if x is None:
            return None
        if self.mesh is not None:
            return jax.device_put(x, NamedSharding(self.mesh, P()))
        return self._put(x)

    @contextlib.contextmanager
    def _on_replica(self):
        """Trace-time context for this replica's dispatches: activates
        the replica submesh (so model-code shard_hint constraints lower
        tensor-parallel collectives) when the replica is a TP group."""
        if self.mesh is None:
            yield
        else:
            with shard.use_mesh(self.mesh):
                yield

    # ------------------------------------------------------- admission --
    def _admit_spec(self, spec: TenantSpec,
                    due_wall: Optional[float] = None) -> Tenant:
        """Create a tenant from a spec (resident at construction or
        arriving mid-run), as one ``serve.admit`` span.  Its TTFT clock
        starts at ``due_wall``, else at the admission's start."""
        cfg = resolve_arch(spec.model)
        aid = spec.model if isinstance(spec.model, str) else cfg.name
        # programs, buckets and prefix entries are keyed by cfg.name:
        # one name must mean one config on this server
        known = self._cfgs.setdefault(cfg.name, cfg)
        if known != cfg:
            raise ValueError(f"two configs named {cfg.name!r}")
        # a spec-pinned seed overrides the admission counter: the fleet
        # router stamps the GLOBAL admission index so replaying one
        # replica's scenario single-device rebuilds the exact same
        # params/prompt/tid (the bit-identical contract)
        i = spec.seed if spec.seed is not None else self._n_admitted
        self._n_admitted += 1
        tid = f"t{i}:{aid}"
        due = due_wall if due_wall is not None else clock()
        stamps = self.spans.task(tid, due)
        with self.spans.span(ADMIT, tid) as sp:
            t = self._new_tenant(spec, cfg, aid, i, tid, due)
        stamps["admit_start"], stamps["admit_end"] = sp.start, sp.end
        if stamps["first_token"] is not None:
            # full prefix hit: its first token came with the admission
            stamps["first_dispatch"] = sp.end
        return t

    def _new_tenant(self, spec: TenantSpec, cfg: ArchConfig, aid: str,
                    i: int, tid: str, due: float) -> Tenant:
        """Prompt tenants get deterministic prompt tokens, a
        prefill-block TenantTask for chunk scheduling, and a
        KV-working-set page reservation held until departure."""
        # a spec-pinned param_seed decouples MODEL identity from tenant
        # identity: every session on one system prompt shares a params
        # instance — the precondition for cross-tenant KV dedup
        pkey = spec.param_seed if spec.param_seed is not None else i
        params = self._put_params(
            M.init_params(cfg, jax.random.PRNGKey(pkey)))
        if cfg.name not in self._step_fns:
            # plan is static: each (arch, plan) pair compiles once
            # and is cached; the grant decides which kernels run
            self._step_fns[cfg.name] = jax.jit(
                M.make_decode_step(cfg),
                static_argnames=("plan", "kv_len"))
        tm = TenantModel(_ffn_graph(aid, cfg, seq_block=self.batch),
                         self.mapper)
        self._align_lbm_to_vmem(tm, cfg, max(self.batch, LANE))
        task = TenantTask(tid, tm, self.cache, self.nec, self.policy,
                          replica=self.replica)
        enc = self._put_replicated(
            jnp.zeros((self.batch, cfg.enc_len, cfg.d_model), cfg.jdtype)
            if cfg.family == "encdec" else None)
        t = Tenant(tid, cfg, params, None, self._step_fns[cfg.name], task,
                   token=None, enc=enc)
        t.budget_left = spec.n_inferences
        if spec.qos_ms is not None:
            self.qos_targets[tid] = spec.qos_ms * 1e-3
        # QoS target pinned ONCE at admission (most-specific pattern
        # match) — _slack reads the resolved value every epoch instead
        # of re-running the match per tenant per epoch
        t.qos_target = self._resolve_qos(tid)
        hit = None
        if spec.prompt_len > 0:
            # the KV cache must hold the prompt plus every budgeted
            # decode step: dynamic_update_slice CLAMPS out-of-range
            # writes, so decoding past max_len would silently corrupt
            # the last cache slot instead of erroring
            need = spec.prompt_len + (spec.n_inferences or 0)
            assert need <= self.max_len, \
                (f"{tid}: prompt {spec.prompt_len} + decode budget "
                 f"{spec.n_inferences or 0} > max_len {self.max_len}")
            t.prompt_len = spec.prompt_len
            t.prompt = _prompt_tokens(spec, i, cfg, self.batch)
            t.kv_dtype = self._choose_kv_dtype(cfg, spec)
            # whole-prompt MCT for the sequential baseline, chunk-block
            # MCT for interleaved chunked prefill
            pf_block = (spec.prompt_len
                        if self.admission == "sequential" or not self.pipeline
                        else self.prefill_block)
            ptm = TenantModel(_ffn_graph(aid, cfg, seq_block=pf_block),
                              self.mapper)
            self._align_lbm_to_vmem(ptm, cfg, max(pf_block, LANE))
            t.ptask = TenantTask(tid + "/pf", ptm, self.cache, self.nec,
                                 self.policy, replica=self.replica)
            want = _kv_reserve_pages(cfg, self.batch, spec.prompt_len,
                                     t.kv_dtype)
            t.kv_wanted = want
            shared: List[int] = []
            if self._dedup_eligible(spec, cfg):
                t.dedup = (cfg.name, _params_key(spec, t.kv_dtype))
                hit = self._prefix_lookup(t)
            if hit is not None:
                # attach BEFORE allocating the private remainder: the
                # refcount protects the matched chain from the very
                # pool pressure that allocation can trigger
                t.prefix_key = hit.key
                t.prefix_hit = hit.kv_len
                self.prefix.attach(hit.key, tid)
                shared = self.cache.share(self.prefix.chain_pages(hit),
                                          tid + "#kv")
                # one dynamic-update-slice copy of the shared prefix
                # into fresh zero caches: bit-identical to the state a
                # cold tenant reaches after prefilling the same tokens
                t.caches = self._put_caches(self._seed_fn(cfg, t.kv_dtype)(
                    hit.payload["snap"], prefix_len=hit.kv_len))
                t.pf_pos = hit.kv_len
            # best-effort KV reservation (for the un-shared remainder):
            # the pool's pressure hook may reclaim cold prefixes to
            # meet it in full, else degrade to what the pool can spare
            # now — kv_reserved < kv_wanted records the degradation
            priv = max(0, want - len(shared))
            got = self.cache.alloc(tid + "#kv", priv)
            if got is None:
                got = self.cache.alloc(tid + "#kv",
                                       min(priv, self.cache.free_pages))
            t.kv_reserved = len(shared) + len(got or [])
        else:
            # legacy seed-token flow: no prompt, decode from token 0
            t.token = self._put_replicated(
                jnp.full((self.batch, 1), i % cfg.vocab_size, jnp.int32))
        if t.caches is None:
            t.caches = self._put_caches(
                init_caches(params, cfg, self.batch, self.max_len,
                            kv_dtype=t.kv_dtype))
        t.admitted_wall = due
        self.tenants.append(t)
        self._unstack_bucket(cfg.name)
        self._groups.setdefault(cfg.name, []).append(t)
        self._epoch_cores.setdefault(cfg.name, M.make_decode_epoch(cfg))
        self._prefill_cores.setdefault(cfg.name, M.make_prefill_chunk(cfg))
        self._batched.pop(cfg.name, None)   # group changed: stack stale
        if hit is not None and t.pf_pos >= t.prompt_len:
            # full hit: the whole prompt is resident and the entry
            # stored the producer's first decode token — prefill is
            # skipped entirely and TTFT collapses to the seeding copy
            tok = hit.payload["token"]
            self._finish_prefill(t, tok)
            self._stamp_ttft(t, tok)
        if self.aot_warmup and self._run_steps > 0:
            # mid-run arrival: extend the AOT universe with the new
            # tenant's (plans, k, kv) trajectory while its prompt is
            # still prefilling
            self.warm_aot(self._run_steps)
        return t

    def _dedup_eligible(self, spec: TenantSpec, cfg: ArchConfig) -> bool:
        """Cross-tenant KV dedup preconditions: the server opted in, a
        session spec with decoupled param/prompt identities (content
        that can actually recur across tenants), a prompt to dedup, an
        arch whose prompt prefix determines its cache prefix (encdec
        caches are encoder-derived, not prompt-derived), and the
        interleaved pipelined path (chunked prefill is what can resume
        mid-prompt)."""
        return (self.prefix_dedup and self.pipeline
                and self.admission == "interleaved"
                and spec.param_seed is not None
                and spec.prompt_seed is not None
                and spec.prompt_len > 0 and cfg.family != "encdec")

    def _prefix_lookup(self, t: Tenant):
        """Longest USABLE resident prefix for an arriving prompt.  A
        partial hit must sit on the tenant's chunk-alignment grid (the
        chunked == one-shot bitwise contract only covers aligned
        boundaries), and a full hit must carry the stored first decode
        token; anything else walks up the parent chain."""
        arch, params_key = t.dedup
        align = self._chunk_align(t.cfg)
        cands = _prefix_candidates(t.prompt, t.prompt_len, align)
        ent = self.prefix.lookup(arch, params_key, cands)
        while ent is not None:
            if ent.kv_len == t.prompt_len:
                if ent.payload.get("token") is not None:
                    return ent
            elif ent.kv_len % align == 0:
                return ent
            ent = (self.prefix.entries.get(ent.parent)
                   if ent.parent is not None else None)
        return None

    def _choose_kv_dtype(self, cfg: ArchConfig, spec: TenantSpec) -> str:
        """KV storage precision for an arriving prompt tenant.  SSM
        decode carries recurrent fp state, not row-addressed KV — never
        quantized.  A fixed server policy pins the rung; ``auto`` prices
        the full reservation at every rung of the precision ladder and
        takes the first that fits the pool's current free pages
        (falling through to the narrowest) — the paper's residency
        pressure expressed as a precision downgrade instead of a
        partial reservation."""
        if cfg.family == "ssm" or cfg.family == "encdec":
            return "native"
        if self.kv_dtype != "auto":
            return self.kv_dtype
        want = {kv: _kv_reserve_pages(cfg, self.batch, spec.prompt_len, kv)
                for kv in KV_PRECISION_LADDER}
        return choose_kv_dtype(want, self.cache.free_pages)

    def _seed_fn(self, cfg: ArchConfig, kv_dtype: str = "native"):
        """Jitted prefix-seeding program, one per (arch, KV precision)
        (jit keys the static prefix_len variants).  The snapshot
        argument is NOT donated: the resident entry keeps serving later
        arrivals."""
        key = (cfg.name, kv_dtype)
        fn = self._seed_jits.get(key)
        if fn is None:
            def seed(snap, prefix_len):
                return seed_caches_from_prefix(cfg, self.batch,
                                               self.max_len, snap,
                                               prefix_len,
                                               kv_dtype=kv_dtype)
            fn = jax.jit(seed, static_argnames=("prefix_len",))
            self._seed_jits[key] = fn
        return fn

    def _batched_params(self, name: str):
        """Tenant-stacked params for a bucketed arch group, built
        LAZILY on the first dispatch of an actual bucket and cached
        while the group membership holds — an admission/departure of a
        never-bucketing arrival must not pay (or retain) an
        O(param bytes) restack of the whole group."""
        stacked = self._batched.get(name)
        if stacked is None:
            ts = self._groups[name]
            stacked = self._batched[name] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[t.params for t in ts])
            self._batched_cores.setdefault(
                name, M.make_decode_epoch_batched(ts[0].cfg))
        return stacked

    def _due(self, item: List) -> bool:
        return item[2] <= self._clock

    def _admit_due(self, steps: int) -> None:
        """Admission control, checked at epoch boundaries.  Requests
        whose arrive_at (mapped onto the logical step clock) has passed
        are stamped as *due* — their TTFT clock starts — and then:

        * ``interleaved`` (continuous batching): admitted immediately;
          their prompt chunks join the next epoch alongside everyone
          else's decode.
        * ``sequential`` (static batching, the measured baseline):
          admitted only at a batch boundary — when every in-flight
          tenant has drained its decode work — and then prefilled
          whole-prompt, FCFS.  The queue wait counts against TTFT.
        """
        now = clock()
        for item in self._queue:
            if item[1] is None and self._due(item):
                item[1] = now
        continuous = self.pipeline and self.admission == "interleaved"
        if not continuous:
            busy = any((not t.departed and t.prefilling)
                       or self._decodable(t, steps)
                       for t in self.tenants)
            if busy:
                return
        while self._queue and self._due(self._queue[0]):
            spec, due_wall, arrive_step = self._queue[0]
            # malformed/oversized prompts (fault-injected or hostile)
            # are shed at the door, never asserted on mid-admission
            bad = self._malformed(spec)
            if bad is not None:
                self._queue.pop(0)
                self._shed(spec, due_wall, reason=bad)
                continue
            if self.queue_deadline_s is not None:
                # deadline measured from the ORIGINAL arrival step, not
                # the latest retry step a deferral pushed item[2] to
                orig = self._defer_attempts.get(id(spec),
                                                (0, arrive_step))[1]
                decision = self._overload_decision(spec, orig)
                if decision == "defer":
                    self._defer_head()
                    continue
                if decision == "shed":
                    self._queue.pop(0)
                    self._shed(spec, due_wall, reason="deadline")
                    continue
            spec, due_wall, _ = self._queue.pop(0)
            self._defer_attempts.pop(id(spec), None)
            self._admit_spec(spec, due_wall)

    def _malformed(self, spec: TenantSpec) -> Optional[str]:
        """Reject-reason for a spec the server cannot possibly serve
        (the fault harness injects these; admission must shed them
        gracefully instead of tripping internal asserts)."""
        if spec.prompt_len < 0:
            return "negative_prompt"
        if spec.prompt_len > _PROMPT_CAP and spec.prompt_seed is not None:
            return "prompt_over_cap"
        if spec.prompt_len > 0:
            need = spec.prompt_len + (spec.n_inferences or 0)
            if need > self.max_len:
                return "oversized_prompt"
        return None

    def _overload_decision(self, spec: TenantSpec,
                           arrive_step: int) -> str:
        """Deadline-aware backpressure ladder for one due arrival:

        * the pool can back the spec's KV reservation at the CHEAPEST
          precision rung -> ``admit`` (the ladder walk / best-effort
          shrink in _admit_spec handles the rest of the degradation);
        * it can't, but the arrival's queue deadline still has slack ->
          ``defer`` with jittered backoff;
        * deadline blown -> ``shed`` if this is (one of) the
          lowest-QoS arrivals waiting, else force-admit degraded — a
          strict-SLO tenant is never starved behind best-effort ones."""
        cfg = resolve_arch(spec.model)
        if spec.prompt_len <= 0:
            return "admit"
        floor_kv = ("native" if cfg.family in ("ssm", "encdec")
                    or self.kv_dtype == "native"
                    else (self.kv_dtype if self.kv_dtype != "auto"
                          else KV_PRECISION_LADDER[-1]))
        want = _kv_reserve_pages(cfg, self.batch, spec.prompt_len, floor_kv)
        if want <= self.cache.free_pages:
            return "admit"
        deadline = max(1, int(math.ceil(self.queue_deadline_s
                                        * self.steps_per_s)))
        if self._clock - arrive_step < deadline:
            return "defer"
        loose = (lambda s: math.inf if s.qos_ms is None else s.qos_ms)
        if loose(spec) >= max(loose(it[0]) for it in self._queue):
            return "shed"
        return "admit"

    def _defer_head(self) -> None:
        """Push the head arrival back by a jittered backoff delay (its
        due_wall TTFT stamp survives — deferral time counts against
        TTFT, exactly like a sequential-admission queue wait)."""
        item = self._queue.pop(0)
        spec = item[0]
        att, orig = self._defer_attempts.get(id(spec), (0, item[2]))
        self._defer_attempts[id(spec)] = (att + 1, orig)
        delay = self.backoff.delay_s(att, key=orig)
        item[2] = max(self._clock + 1,
                      self._clock + int(math.ceil(delay * self.steps_per_s)))
        self._queue.append(item)
        self._queue.sort(key=lambda it: it[2])
        self.deferrals += 1
        self.fault_log.record(self._clock, "defer",
                              model=str(getattr(spec.model, "name",
                                                spec.model)),
                              attempt=att + 1, retry_step=item[2])

    def _shed(self, spec: TenantSpec, due_wall: Optional[float],
              reason: str) -> None:
        """Reject one arrival (overload or malformed): recorded, never
        admitted — the terminal SHED state of the admission machine."""
        self._defer_attempts.pop(id(spec), None)
        aid = (spec.model if isinstance(spec.model, str)
               else getattr(spec.model, "name", str(spec.model)))
        self.shed.append({
            "model": aid, "state": STATE_SHED, "reason": reason,
            "step": self._clock, "qos_ms": spec.qos_ms,
            "prompt_len": spec.prompt_len,
            "waited_s": (clock() - due_wall
                         if due_wall is not None else None)})
        self.fault_log.record(self._clock, "shed", model=aid, reason=reason)

    def _depart(self, t: Tenant) -> None:
        """Dynamic tenancy, serving side: the tenant leaves, reclaiming
        its page grants, its KV reservation, and its allocator profiles
        — surviving tenants' next grants (and prefill chunk sizes) grow
        accordingly."""
        if t.departed:
            return
        t.departed = True
        t.task.depart()
        if t.ptask is not None:
            t.ptask.depart()
        if t.prefix_key is not None:
            # refcount-- down the attached chain; the entries (and any
            # page the PRODUCER contributed) stay resident for the next
            # warm arrival until pool pressure evicts them
            self.prefix.detach(t.prefix_key, t.tid)
        if t.ckpt_ref is not None:
            # departing while preempted: drop the parked checkpoint
            if t.ckpt_ref.get("mode") == "snapshot":
                shutil.rmtree(t.ckpt_ref["dir"], ignore_errors=True)
            elif t.ckpt_ref.get("mode") == "prefix":
                self.prefix.detach(t.ckpt_ref["key"], t.tid + "/preempt")
            t.ckpt_ref = None
        self.cache.free(t.tid + "#kv", None)
        self._unstack_bucket(t.cfg.name)
        self._groups[t.cfg.name].remove(t)
        self._batched.pop(t.cfg.name, None)   # group changed: stack stale
        # release the REAL device buffers too, not just the modeled
        # pages: a long-running server under open-loop arrivals would
        # otherwise accumulate one full param copy + max_len KV cache
        # per departed tenant (outputs/choices stay for the result)
        t.params = None
        t.caches = None
        t.enc = None
        t.prompt = None

    def _process_departures(self) -> None:
        for t in self.tenants:
            if (not t.departed and t.budget_left is not None
                    and t.budget_left <= 0 and not t.prefilling):
                self._depart(t)

    # --------------------------------------------- preempt / resume -----
    def _ckpt_root(self) -> str:
        if self._ckpt_dir is None:
            self._ckpt_dir = tempfile.mkdtemp(prefix="repro-preempt-")
            self._owns_ckpt_dir = True
        return self._ckpt_dir

    def _select_victim(self) -> Optional[Tenant]:
        """Policy-pluggable QoS-aware victim selection over the tenants
        that CAN be preempted: decoding (not mid-prefill — a prompt in
        flight holds chunk state the snapshot does not cover), not
        already preempted, not departed."""
        cands = [(t.tid, t.qos_target,
                  t.kv_reserved + t.task.held_pages, t.tokens_served)
                 for t in self.tenants
                 if not t.departed and t.state != STATE_PREEMPTED
                 and t.token is not None and not t.prefilling]
        tid = self.preemption_policy.select(cands)
        if tid is None:
            return None
        return next(t for t in self.tenants if t.tid == tid)

    def preempt_tenant(self, t: Tenant, resume_after_epochs: int = 1,
                       reason: str = "fault") -> bool:
        """Pause one decode tenant bit-preservingly: checkpoint its KV
        caches + decode cursor, free every page it holds back into the
        pool, and schedule a resume.  Two snapshot paths:

        * **prefix re-seed** — the tenant sits exactly at the end of a
          registered full-prompt prefix entry (index == prompt_len, no
          decode step taken): the resident entry IS the checkpoint, so
          nothing is copied; a refcount hold keeps it resident across
          the preemption window.
        * **checkpoint snapshot** — general case: the caches + feedback
          token are host-gathered through checkpoint.save (exact bytes
          for every float32/int8/fp8 leaf), restored on resume.

        Resume is bit-identical to never having been preempted: decode
        is a pure function of (caches, token, index), the cursor is
        preserved, and the KV attention windows re-derive from it."""
        if (t.departed or t.state == STATE_PREEMPTED or t.prefilling
                or t.token is None):
            return False
        # the tenant may be holding its caches inside a stacked bucket
        self._unstack_bucket(t.cfg.name)
        ent = None
        if t.dedup is not None and t.prompt is not None:
            full_key = self.prefix.prefix_key(
                t.dedup[0], t.dedup[1], t.prompt.tobytes())
            ent = self.prefix.entries.get(full_key)
        if (ent is not None and t.index == t.prompt_len
                and ent.payload.get("token") is not None):
            self.prefix.attach(ent.key, t.tid + "/preempt")
            t.ckpt_ref = {"mode": "prefix", "key": ent.key}
        else:
            root = os.path.join(
                self._ckpt_root(),
                t.tid.replace("/", "_").replace(":", "_"))
            ckpt.save(root, t.index,
                      {"caches": t.caches, "token": t.token},
                      extra={"index": t.index, "pf_pos": t.pf_pos})
            t.ckpt_ref = {"mode": "snapshot", "dir": root,
                          "step": t.index}
        # surrender the device buffers and every modeled page: decode
        # grants (task), the KV reservation, and the attached prefix
        # chain refcounts — survivors' grants grow into the freed space
        t.task.preempt()
        if t.prefix_key is not None:
            self.prefix.detach(t.prefix_key, t.tid)
            t.prefix_key = None
        self.cache.free(t.tid + "#kv", None)
        t.caches = None
        t.token = None
        t.state = STATE_PREEMPTED
        t.preemptions += 1
        t.preempted_wall = clock()
        t.resume_step = (self._clock
                         + max(1, resume_after_epochs) * self.epoch_len)
        self.fault_log.record(self._clock, "preempt", tid=t.tid,
                              mode=t.ckpt_ref["mode"], reason=reason,
                              resume_step=t.resume_step)
        return True

    def _try_resume(self) -> None:
        """Resume every preempted tenant whose resume step has passed —
        called at epoch boundaries, before planning, so a resumed
        tenant decodes in the same epoch it rejoins."""
        for t in self.tenants:
            if (not t.departed and t.state == STATE_PREEMPTED
                    and t.resume_step is not None
                    and self._clock >= t.resume_step):
                self._resume_tenant(t)

    def _resume_tenant(self, t: Tenant) -> bool:
        """Rebuild a preempted tenant's device state bit-identically and
        re-admit it to scheduling: re-reserve KV pages (best-effort,
        like admission), restore caches + feedback token from the
        snapshot, re-attach the allocator profile."""
        ref = t.ckpt_ref
        assert t.state == STATE_PREEMPTED and ref is not None, t.tid
        want = t.kv_wanted
        shared: List[int] = []
        if ref["mode"] == "prefix":
            ent = self.prefix.entries[ref["key"]]
            self.prefix.attach(ref["key"], t.tid)
            t.prefix_key = ref["key"]
            shared = self.cache.share(self.prefix.chain_pages(ent),
                                      t.tid + "#kv")
            t.caches = self._put_caches(self._seed_fn(t.cfg, t.kv_dtype)(
                ent.payload["snap"], prefix_len=ent.kv_len))
            t.token = self._put_replicated(ent.payload["token"])
            self.prefix.detach(ref["key"], t.tid + "/preempt")
        else:
            like = {"caches": jax.eval_shape(
                        lambda: init_caches(t.params, t.cfg, self.batch,
                                            self.max_len,
                                            kv_dtype=t.kv_dtype)),
                    "token": jax.ShapeDtypeStruct((self.batch, 1),
                                                  jnp.int32)}
            tree, _ = ckpt.restore(ref["dir"], like, step=ref["step"])
            t.caches = self._put_caches(tree["caches"])
            t.token = self._put_replicated(tree["token"])
            shutil.rmtree(ref["dir"], ignore_errors=True)
        priv = max(0, want - len(shared))
        got = self.cache.alloc(t.tid + "#kv", priv)
        if got is None:
            got = self.cache.alloc(t.tid + "#kv",
                                   min(priv, self.cache.free_pages))
        t.kv_reserved = len(shared) + len(got or [])
        t.ckpt_ref = None
        t.resume_step = None
        t.task.resume()
        t.state = STATE_RESUMED
        if t.preempted_wall is not None:
            t.recovery_s.append(clock() - t.preempted_wall)
            t.preempted_wall = None
        self.fault_log.record(self._clock, "resume", tid=t.tid,
                              kv_reserved=t.kv_reserved)
        return True

    # --------------------------------------------- fault injection ------
    def _apply_due_faults(self, steps: int) -> None:
        """Epoch-boundary fault hook: release expired pressure holds,
        then fire every due event of the installed plan."""
        for h in list(self._pressure_holds):
            if h[0] <= self._clock:
                self.cache.free(h[1], None)
                self._pressure_holds.remove(h)
                self.fault_log.record(self._clock, "pressure_release",
                                      holder=h[1])
        if self.faults is None:
            return
        for e in self.faults.due(self._clock):
            self.inject(e, steps)

    def inject(self, e: FaultEvent, steps: int = 0) -> None:
        """Apply one fault event NOW (the fleet driver forwards events
        to the target replica through this entry point)."""
        if e.kind == "pool_pressure":
            holder = f"fault#p{self._pressure_n}"
            self._pressure_n += 1
            # allocate THROUGH the pool so the pressure hook fires —
            # cold prefix entries get reclaimed exactly as they would
            # under a real grant burst
            got = self.cache.alloc(holder, e.pages)
            if got is None:
                got = self.cache.alloc(
                    holder, min(e.pages, self.cache.free_pages))
            self._pressure_holds.append(
                [self._clock + max(1, e.hold_epochs) * self.epoch_len,
                 holder])
            self.fault_log.record(self._clock, "pool_pressure",
                                  seized=len(got or []),
                                  free_after=self.cache.free_pages)
            if self.cache.free_pages == 0:
                # spike emptied the pool outright: preempt one victim
                # so co-tenants keep decoding instead of starving
                v = self._select_victim()
                if v is not None:
                    self.preempt_tenant(v, e.hold_epochs,
                                        reason="pool_pressure")
        elif e.kind == "straggler":
            self._straggler_left = max(self._straggler_left,
                                       max(1, e.hold_epochs))
            self._straggler_factor = e.factor
            self.fault_log.record(self._clock, "straggler",
                                  epochs=e.hold_epochs, factor=e.factor)
        elif e.kind == "bad_prompt":
            spec = e.spec
            if spec is None:
                aid = e.target if isinstance(e.target, str) else "yi-9b"
                spec = TenantSpec(aid, prompt_len=4 * self.max_len,
                                  n_inferences=2)
            self.fault_log.record(self._clock, "bad_prompt",
                                  prompt_len=spec.prompt_len)
            self._queue.append([spec, None, self._clock])
            self._queue.sort(key=lambda it: it[2])
        elif e.kind == "preempt":
            t = None
            if e.target is not None:
                t = next((x for x in self.tenants if x.tid == e.target),
                         None)
            if t is None:
                t = self._select_victim()
            if t is not None:
                self.preempt_tenant(t, e.hold_epochs, reason="injected")
        # replica_kill is fleet-level: a standalone server ignores it

    def _observe_epoch(self) -> None:
        """Feed the straggler detector one epoch observation.  Armed
        only under an installed fault plan, and fed a LOGICAL duration
        (1.0 per clean epoch, x factor while an injected straggler is
        active) so detection and mitigation are deterministic.  A trip
        preempts the policy-selected victim — shedding load off the
        straggling replica — and resets the strike counter."""
        if self.faults is None:
            return
        dt = 1.0
        if self._straggler_left > 0:
            self._straggler_left -= 1
            dt = self._straggler_factor
        if self.straggler.observe(self.spans.epoch, dt):
            self.straggler.strikes = 0
            v = self._select_victim()
            self.fault_log.record(self._clock, "straggler_trip",
                                  victim=v.tid if v else None)
            if v is not None:
                self.preempt_tenant(v, reason="straggler")

    def _wake_steps(self) -> List[int]:
        """Every future logical step that can create new work while the
        current epoch is idle: queued arrivals, scheduled resumes,
        pressure-hold releases, unfired fault events.  The idle
        fast-forward jumps to the earliest of these instead of
        terminating the run with tenants still preempted."""
        wake = [it[2] for it in self._queue]
        wake += [t.resume_step for t in self.tenants
                 if not t.departed and t.state == STATE_PREEMPTED
                 and t.resume_step is not None]
        wake += [h[0] for h in self._pressure_holds]
        if self.faults is not None:
            nxt = self.faults.peek_step()
            if nxt is not None:
                wake.append(nxt)
        return wake

    def _align_lbm_to_vmem(self, tm: TenantModel, cfg: ArchConfig,
                           seq_block: int) -> None:
        """Make the LBM candidates quote the *fused kernel's* VMEM
        working set: on the VMEM substrate a block grant must admit the
        block_fused_ffn claim, or the lowering would silently demote
        every granted LBM selection back to tiled LWM kernels.  Quoted
        for the REAL d_ff (:func:`_ffn_width`) — the dimension the
        kernel executes with (block_fused_ffn asserts d_ff % block_f ==
        0).

        Copy-on-write: the TenantModel's mapping may be the process-wide
        memoized instance shared with other tenants/servers, so the
        aligned MCTs go into a fresh ModelMapping instead of mutating
        the shared one."""
        eb = _elem_bytes(cfg)
        need = fused_ffn_pages(seq_block, cfg.d_model, _ffn_width(cfg), eb)
        mcts = []
        for mct in tm.mapping.mcts:
            if mct.lbm is not None and mct.lbm.p_need < need:
                mct = MCT(mct.layer_name, list(mct.lwms),
                          dataclasses.replace(mct.lbm, p_need=need))
            mcts.append(mct)
        tm.mapping = ModelMapping(tm.mapping.model_name, mcts,
                                  tm.mapping.blocks)

    # ------------------------------------------------------ scheduling --
    def _schedule_block(self, t: Tenant, now: float,
                        task: Optional[TenantTask] = None
                        ) -> List[Tuple[Selection, int]]:
        """Run a tenant block through the unified TenantTask state
        machine: select -> (timeout-downgrade)* -> grant -> end,
        charging traffic through the NEC ledger (folded by the task's
        ``charge_repeat`` when the grant covers a whole epoch).
        ``task`` defaults to the tenant's decode-block task; the chunked
        prefill path passes the prefill-block task instead.  Returns,
        per layer, the final Selection and the pages actually held at
        execution — the inputs the KernelPlan lowering consumes."""
        task = task or t.task
        if task.done:
            task.reset_for_next_inference()
        sched: List[Tuple[Selection, int]] = []
        while not task.done:
            sel = task.begin_layer(now)
            granted = self.cache.alloc(task.id, task.pages_to_request())
            attempts = 0
            while granted is None and attempts < len(task.mct().lwms) + 2:
                # synchronous serving loop: a failed grant downgrades
                # immediately (the simulator waits out t_ahead instead)
                sel = task.on_timeout(now)
                granted = self.cache.alloc(task.id, task.pages_to_request())
                attempts += 1
            if granted is None:
                # starved: stream the layer with whatever is already
                # held.  Pick the minimum-footprint LWM explicitly
                # (min over p_need, not positional lwms[0]) so a
                # starved tenant never streams through a mid-sized tile
                # it holds no pages for.
                smallest = min(task.mct().lwms, key=lambda m: m.p_need)
                sel = Selection(smallest, 0, now)
                task.selection = sel
                granted = []
            task.start_execution(now, granted)
            sched.append((task.selection, task.held_pages))
            t.choices.append(f"{sel.candidate.kind}:{task.held_pages}p")
            task.end_layer(now)
        return sched

    def _lower_plan(self, t: Tenant, sched: List[Tuple[Selection, int]],
                    seq_block: Optional[int] = None) -> KernelPlan:
        """Lower the block's granted selections into the KernelPlan the
        decode step (or prefill chunk) executes.  An LBM grant covers
        the whole block; LWM layers each lower their own GEMM tile from
        their own grant.  Lowered with the REAL d_ff — the dimension
        the kernels execute with — not the padded scheduling-graph one."""
        cfg = t.cfg
        lbm = [(s, p) for s, p in sched if s.candidate.kind == "LBM"]
        sel, pages = lbm[0] if lbm else sched[0]
        down_pages = None if lbm else (sched[-1][1] if len(sched) > 1
                                       else None)
        return lower_selection(
            sel, pages, seq_block=seq_block or max(self.batch, LANE),
            d_model=cfg.d_model, d_ff=_ffn_width(cfg),
            dtype_bytes=_elem_bytes(cfg), head_dim=cfg.hd,
            ssm_chunk=cfg.ssm_chunk, down_pages=down_pages,
            kv_dtype=t.kv_dtype)

    def _schedule_epoch(self, t: Tenant, now: float,
                        k: int) -> Optional[KernelPlan]:
        """CaMDN selection + NEC charging for one tenant's epoch: the
        grant is held for the whole K-step window, so the block's
        traffic is charged once with repeat=K (bit-identical counters to
        per-step charging).  Returns the plan the epoch executes (None
        for SSM decode, whose O(1) recurrent step has no dense FFN — the
        plan only affects prefill there, so we skip the per-plan decode
        recompile)."""
        t.task.charge_repeat = k
        try:
            sched = self._schedule_block(t, now)
        finally:
            t.task.charge_repeat = 1
        plan = self._lower_plan(t, sched)
        t.plans.append(plan)
        return self._dec_plan(t, plan)

    def _dec_plan(self, t: Tenant, plan: KernelPlan) -> Optional[KernelPlan]:
        """The plan actually bound (statically) to the decode step.
        SSM decode is O(1)-recurrent — no dense FFN — and MoE decode
        routes its one token through the gathered-expert fast path
        (``moe._decode_moe``): a mapping plan has no tiling freedom at
        M=1, so neither family's decode recompiles per plan.  The grant
        still governs their prefill kernels, the NEC charging, and the
        recorded plan trace; dense/hybrid/encdec decode executes the
        plan-lowered FFN kernels as before."""
        if t.cfg.family == "ssm" or t.cfg.is_moe:
            return None
        return plan

    def _chunk_align(self, cfg: ArchConfig) -> int:
        """Interior prefill-chunk boundaries stay on the LANE grid, and
        for SSM/hybrid archs also on SSD chunk boundaries — the
        alignment the chunked == one-shot bitwise contract needs."""
        if cfg.family in ("ssm", "hybrid") and cfg.ssm_chunk > 0:
            return LANE * cfg.ssm_chunk // math.gcd(LANE, cfg.ssm_chunk)
        return LANE

    def _plan_prefill_chunk(self, t: Tenant, now: float) -> Tuple:
        """Schedule ONE cache-aware prefill chunk: renegotiate the
        tenant's grant through the prefill-block MCT (NEC-charged per
        chunk via charge_and_plan), lower the granted Selection into a
        KernelPlan, and lower THAT into the chunk length the grant
        admits.  Returns the epoch work item."""
        sched = self._schedule_block(t, now, task=t.ptask)
        plan = self._lower_plan(t, sched, seq_block=self.prefill_block)
        t.plans.append(plan)
        chunk = lower_prefill_chunk(
            plan, d_model=t.cfg.d_model, d_ff=_ffn_width(t.cfg),
            dtype_bytes=_elem_bytes(t.cfg),
            align=self._chunk_align(t.cfg), max_tokens=self.prefill_block,
            remaining=t.prompt_len - t.pf_pos)
        t.chunks.append(chunk)
        return ("prefill", t, plan, chunk)

    def _finish_prefill(self, t: Tenant, token: Any) -> None:
        """The final chunk's greedy token flips the tenant to decode:
        seed the feedback loop and retire the prefill task.  The TTFT
        stamp (which blocks on the token) is the caller's job — the
        epoch dispatcher defers it until AFTER the epoch's decode items
        are dispatched, so admission never stalls the decode pipeline."""
        t.token = token
        t.outputs.append(token)
        t.tokens_served += self.batch
        t.index = t.prompt_len
        t.ptask.depart()
        if t.dedup is not None:
            self._register_prefix(t, token)

    def _register_prefix(self, t: Tenant, token: Any) -> None:
        """Producer side of the dedup: publish the finished prompt's KV
        as a chain of PrefixIndex entries at chunk-grid granularity.

        Causal attention never rewrites earlier KV rows, so ONE copied
        snapshot of the final caches is a valid payload for every
        interior boundary (the seeder slices rows ``[0, p)``); SSM /
        hybrid recurrent state is cumulative — only the exact
        full-length entry is registered for them.  Each entry holds the
        slice of the tenant's KV reservation its length-delta accounts
        for, so the modeled pages survive the producer's departure.
        The full-length entry also stores the first decode token, which
        is what lets an identical re-arrival skip prefill outright."""
        arch, params_key = t.dedup
        full_key = self.prefix.prefix_key(arch, params_key,
                                          t.prompt.tobytes())
        if full_key in self.prefix.entries:
            # identical prompt already published (e.g. this tenant was
            # itself a full hit): refresh its LRU stamp, no new copy
            self.prefix.touch(full_key)
            return
        # explicit device copy: the live caches are donated to the next
        # decode epoch, the snapshot must outlive the tenant
        snap = jax.tree_util.tree_map(jnp.copy, t.caches)
        align = self._chunk_align(t.cfg)
        if t.cfg.family in ("dense", "moe"):
            bounds = list(range(align, t.prompt_len, align))
            bounds.append(t.prompt_len)
        else:
            bounds = [t.prompt_len]
        resv = sorted(self.cache.pages_of(t.tid + "#kv"))
        parent, prev_pages = None, 0
        for p in bounds:
            budget = min(_kv_reserve_pages(t.cfg, self.batch, p,
                                           t.kv_dtype),
                         len(resv))
            payload = {"snap": snap,
                       "token": token if p == t.prompt_len else None}
            parent = self.prefix.register(
                arch, params_key, t.prompt[:, :p].tobytes(), p,
                resv[prev_pages:budget], payload, parent=parent)
            prev_pages = max(prev_pages, budget)

    def _stamp_ttft(self, t: Tenant, token: Any) -> None:
        with self.spans.span(FIRST_TOKEN, t.tid) as sp:
            jax.block_until_ready(token)
        t.ttft = sp.end - t.admitted_wall
        self.spans.stamp(t.tid, "first_token", sp.end)
        self._record_page_scales(t)

    def _record_page_scales(self, t: Tenant) -> None:
        """Per-page dequant scales for a quantized tenant, recorded at
        the TTFT stamp — the one point the serving loop already blocks
        on a device value, so the host read adds no new sync.  The
        modeled page table has no row map, so the live prefix rows fold
        onto the tenant's reserved pages by an even split; each page
        stores the max per-row scale it covers, a dequant error bound
        readable from the page table without touching the HBM rows."""
        if t.kv_dtype == "native" or t.caches is None or t.pf_pos <= 0:
            return
        pages = sorted(self.cache.pages_of(t.tid + "#kv"))
        if not pages:
            return
        leaves = [np.asarray(x) for path, x in
                  jax.tree_util.tree_flatten_with_path(t.caches)[0]
                  if any(str(getattr(k, "key", "")).endswith("_scale")
                         for k in path)]
        if not leaves:
            return
        live, n = t.pf_pos, len(pages)
        # fold every scale leaf to one max per live row: time axis is
        # ndim-3 for both per-group 4D and stacked 5D scale buffers
        rows = np.stack([
            np.moveaxis(leaf, leaf.ndim - 3, 0)[:live].reshape(live, -1)
            .max(axis=1) for leaf in leaves]).max(axis=0)
        for j, p in enumerate(pages):
            lo = j * live // n
            hi = max(lo + 1, (j + 1) * live // n)
            self.cache.set_page_scale(p, float(rows[lo:hi].max()))

    def _prefill_whole(self, t: Tenant, now: float) -> None:
        """Sequential-admission baseline (and the serial reference
        loop's prompt path): the whole prompt prefills as ONE exclusive
        synchronous device call — scheduled through the whole-prompt
        MCT, so an over-sized working set visibly degrades to small
        tiles — and decode epochs stall behind it (head-of-line)."""
        sched = self._schedule_block(t, now, task=t.ptask)
        plan = self._lower_plan(t, sched, seq_block=t.prompt_len)
        t.plans.append(plan)
        t.chunks.append(t.prompt_len)
        kv = self._kv_len(t.prompt_len)
        fn = self._prefill_fn(t.cfg.name)
        self.spans.stamp(t.tid, "first_dispatch", clock())
        with self._on_replica():
            tok, t.caches = fn(t.params, t.caches,
                               jnp.asarray(t.prompt), jnp.int32(0), t.enc,
                               kv_len=kv)
        t.pf_computed += t.prompt_len
        t.pf_pos = t.prompt_len
        self._finish_prefill(t, tok)
        self._stamp_ttft(t, tok)

    def _sequential_prefills_due(self, now: float) -> None:
        """Head-of-line admission: prefill every pending prompt to
        completion (FCFS) before the next decode epoch is planned."""
        for t in self.tenants:
            if not t.departed and t.prefilling:
                self._prefill_whole(t, now)

    def _remaining(self, t: Tenant, steps: int) -> int:
        if t.budget_left is not None:
            return max(0, t.budget_left)
        return max(0, steps - t.run_steps)

    def _decodable(self, t: Tenant, steps: int) -> bool:
        """Tenant has decode work this run: active, past prefill (the
        feedback token exists), budget/steps left.  THE runnable
        predicate — shared by admission gating, epoch planning, and the
        serial loop so the three can never disagree."""
        return (not t.departed and t.token is not None
                and self._remaining(t, steps) > 0)

    def _epoch_k(self, t: Tenant, steps: int) -> int:
        """Decode window for this tenant's next epoch.  Epochs never
        straddle a KV-window boundary: every step of the epoch shares
        one static kv_len, computed from THIS tenant's index (tenants
        admit at different times with different prompt lengths)."""
        k = min(self.epoch_len, self._remaining(t, steps),
                LANE - (t.index % LANE))
        assert t.index + k <= self.max_len, \
            f"{t.tid}: decode past max_len {self.max_len}"
        return k

    # --------------------------------------- batched Algorithm 1 --------
    def _plan_decode_run(self, run: List[Tenant], now: float, steps: int,
                         dec_plans: Dict[str, Tuple]) -> bool:
        """Batched Algorithm 1 over a contiguous run of decode tenants:
        simulate EVERY tenant's whole-graph grant sequence upfront (one
        ``select_batch`` numpy pass per layer depth, pure), price every
        layer in one vectorized NEC pass, then commit tenant-major —
        replaying the per-tenant oracle's exact order of grants, charges,
        and profile updates, so the Selections and Traffic counters are
        bit-identical to ``_schedule_epoch`` per tenant.

        The simulation is exact because at epoch-plan time the allocator
        is quiescent (every profile's p_alloc == p_next, checked below):
        ``pred_avail_pages`` degenerates to the pool's free count for any
        horizon, each tenant's own intra-block profile churn is excluded
        from its own predictions, and a finished tenant's final profile
        update restores delta-zero before the next tenant selects — so
        every oracle select would have seen exactly the free count the
        batch sees.  Any precondition miss (non-CaMDN policy, carried-over
        pages, a grant the oracle would have had to timeout-downgrade)
        returns False with NOTHING mutated; the caller falls back to the
        oracle."""
        if not isinstance(self.policy, CamdnPolicy):
            return False
        alloc = self.alloc
        if not alloc.quiescent():
            return False
        tasks: List[TenantTask] = []
        for t in run:
            task = t.task
            if task.held_pages != 0 or alloc.has_enabled_lbm(task.id):
                return False
            if not (task.done or task.layer_idx == 0):
                return False
            tasks.append(task)
        F = self.cache.free_pages
        n_layers = [task.model.num_layers for task in tasks]
        # --- pure simulation: all selections, layer by layer ----------
        sels: List[List[Selection]] = [[] for _ in run]
        flags = [False] * len(run)   # simulated per-tenant LBM flag
        held = [0] * len(run)        # pages held at each select point
        for l in range(max(n_layers)):
            idxs = [i for i in range(len(run)) if l < n_layers[i]]
            mcts = [tasks[i].model.mapping.mcts[l] for i in idxs]
            for i, mct in zip(idxs, mcts):
                if flags[i] and mct.lbm is None:
                    # enabled-LBM select with no LBM candidate consults
                    # the pool mid-block — only the oracle models that
                    return False
            blocks = [tasks[i].model.mapping.block_of(l) for i in idxs]
            batch_sels = alloc.select_batch(
                [tasks[i].id for i in idxs], mcts, now,
                [tasks[i].model.layer_t_est[l] for i in idxs],
                [tasks[i].model.block_t_est[b]
                 for i, b in zip(idxs, blocks)],
                [tasks[i].model.mapping.is_head_of_block(l) for i in idxs],
                lbm_enabled=[flags[i] for i in idxs])
            for i, blk, sel in zip(idxs, blocks, batch_sels):
                if max(held[i], sel.p_cur) > F:
                    # the oracle would enter its timeout-downgrade loop
                    return False
                sels[i].append(sel)
                if sel.candidate.kind == "LBM" and l < blk[1] - 1:
                    flags[i], held[i] = True, max(held[i], sel.p_cur)
                else:
                    flags[i], held[i] = False, 0
        ks = [self._epoch_k(t, steps) for t in run]
        if self.lookahead:
            self._lookahead_adjust(run, ks, sels, F)
        # --- one vectorized pricing pass over every (tenant, layer) ---
        items = [(tasks[i], sels[i][l].candidate, l)
                 for i in range(len(run)) for l in range(n_layers[i])]
        priced = price_layer_batch(items, self.policy._price_cache)
        # --- tenant-major commit: the oracle's exact order ------------
        self._batched_runs += 1
        pos = 0
        for i, t in enumerate(run):
            task = tasks[i]
            if task.done:
                task.reset_for_next_inference()
            task.charge_repeat = ks[i]
            sched: List[Tuple[Selection, int]] = []
            try:
                for l in range(n_layers[i]):
                    sel = sels[i][l]
                    task.selection = sel
                    granted = self.cache.alloc(
                        task.id, max(0, sel.p_cur - task.held_pages))
                    assert granted is not None, \
                        f"{task.id}: batched grant infeasible at layer {l}"
                    task.adopt_grant(sel, granted)
                    cand = sel.candidate
                    # CamdnPolicy.on_grant's LBM side effect
                    if (cand.kind == "LBM"
                            and not alloc.has_enabled_lbm(task.id)):
                        alloc.set_lbm(task.id, True)
                        task.lbm_block = task.model.mapping.block_of(l)
                    task.charge(priced[pos + l][1])
                    sched.append((task.selection, task.held_pages))
                    t.choices.append(f"{cand.kind}:{task.held_pages}p")
                    task.end_layer(now)
            finally:
                task.charge_repeat = 1
            pos += n_layers[i]
            plan = self._lower_plan(t, sched)
            t.plans.append(plan)
            dec_plans[t.tid] = (self._dec_plan(t, plan), ks[i])
        return True

    def _simulate_block_sels(self, task: TenantTask, now: float,
                             budget: int) -> Optional[List[Selection]]:
        """Pure what-if Algorithm 1 walk of one task's whole graph under
        a FIXED page budget: the grant sequence the task would receive if
        predicted-available pages were pinned at ``budget`` throughout.
        Returns None when some layer cannot fit even its smallest
        candidate (the oracle would starve-stream it).  Shared by the
        predictive lookahead (budget = next epoch's projected pool) and
        the AOT key predictor (budget = current free pool)."""
        sels: List[Selection] = []
        flag, held = False, 0
        mapping = task.model.mapping
        for l in range(task.model.num_layers):
            mct = mapping.mcts[l]
            blk = mapping.block_of(l)
            if flag and mct.lbm is None:
                return None   # same bail as the batched planner
            if flag:
                sel = Selection(mct.lbm, mct.lbm.p_need, INF)
            elif (mapping.is_head_of_block(l) and mct.lbm is not None
                    and mct.lbm.p_need < budget):
                sel = Selection(
                    mct.lbm, mct.lbm.p_need,
                    now + task.model.block_t_est[blk] * AHEAD_FRACTION)
            else:
                m = mct.best_fit(budget)
                sel = Selection(
                    m, m.p_need,
                    now + task.model.layer_t_est[l] * AHEAD_FRACTION)
            if max(held, sel.p_cur) > budget:
                return None
            sels.append(sel)
            if sel.candidate.kind == "LBM" and l < blk[1] - 1:
                flag, held = True, max(held, sel.p_cur)
            else:
                flag, held = False, 0
        return sels

    # ------------------------------------ predictive grant lookahead ----
    def _upcoming_free_delta(self) -> int:
        """Projected page-pool delta over the NEXT epoch from events that
        are known one epoch early in the logical clock: queued arrivals
        falling due (their KV reservation claims pages), tenants whose
        decode budget expires within the epoch (reservation + grant pages
        free), and prompts completing prefill (their decode stream starts
        claiming grant pages)."""
        delta = 0
        horizon = self._clock + self.epoch_len
        for spec, _, step in self._queue:
            if step > horizon:
                break
            if spec.prompt_len > 0:
                kv = self.kv_dtype if self.kv_dtype != "auto" else "native"
                delta -= _kv_reserve_pages(resolve_arch(spec.model),
                                           self.batch, spec.prompt_len, kv)
        for t in self.tenants:
            if t.departed:
                continue
            if (t.budget_left is not None
                    and 0 < t.budget_left <= self.epoch_len):
                delta += self.cache.allocated_pages(t.tid + "#kv")
                delta += t.task.held_pages
            if t.prefilling and t.prompt_len - t.pf_pos <= self.prefill_block:
                delta -= min(m.p_need
                             for m in t.task.model.mapping.mcts[0].lwms)
        return delta

    def _lookahead_adjust(self, run: List[Tenant], ks: List[int],
                          sels: List[List[Selection]], F: int) -> None:
        """Predictive grant lookahead: epoch s+1's pool pressure is known
        one epoch early (arrivals / departures / prefill completions are
        deterministic in the logical clock).  For the tenants whose
        grants would not survive the projected next-epoch pool, use the
        NEC pricing as a what-if simulator: compare staying on the
        aggressive grant now and being forced down next epoch (plus the
        page re-grant thrash) against taking the stable grant for both
        epochs, and keep whichever projects less DRAM traffic.  Mutates
        only the not-yet-committed selection lists."""
        delta = self._upcoming_free_delta()
        if delta >= 0:
            return
        F_next = max(0, F + delta)
        contested = []
        for i in range(len(run)):
            need = max((s.p_cur for s in sels[i]), default=0)
            if need > F_next:
                contested.append((need - F_next, i))
        contested.sort(reverse=True)
        for shortfall, i in contested[:4]:
            task = run[i].task
            stable = self._simulate_block_sels(task, 0.0, F_next)
            if stable is None:
                continue
            cur_cands = [s.candidate for s in sels[i]]
            stable_cands = [s.candidate for s in stable]
            k = ks[i]
            # stay: aggressive grant this epoch, forced down next epoch,
            # plus the thrashed pages crossing DRAM twice (evict + refill)
            stay = (project_epoch_dram(task, cur_cands, k)
                    + project_epoch_dram(task, stable_cands, k)
                    + shortfall * PAGE_BYTES * 2)
            switch = 2 * project_epoch_dram(task, stable_cands, k)
            if switch < stay:
                sels[i] = stable
                self._lookahead_adjusted += 1

    def _plan_epoch(self, now: float, steps: int) -> List[Tuple]:
        """The epoch planner, recorded as ``serve.plan`` segments around
        the admissions inside it (the host `sched_wall`)."""
        with self.spans.plan():
            return self._plan_epoch_inner(now, steps)

    def _plan_epoch_inner(self, now: float, steps: int) -> List[Tuple]:
        """Host-side scheduling for one epoch: admit due arrivals,
        retire exhausted tenants, then select + charge every active
        tenant's work — a cache-aware prefill chunk for tenants still
        consuming their prompt, a K-step decode window for the rest
        (worst QoS slack first — first claim on the page pool).  Decode
        tenants whose (arch, plan, index, k) coincide bucket into single
        batched calls.  Pure host work: runs one epoch ahead of the
        device.

        Contiguous runs of decode tenants go through the BATCHED
        Algorithm 1 (one numpy pass over the allocator's profile arrays
        for the whole run) when its preconditions hold; anything else —
        and any run failing them — falls back to the per-tenant oracle
        path, preserving the exact sequencing of grants, downgrades, and
        pool-pressure side effects."""
        while True:
            self._apply_due_faults(steps)
            self._try_resume()
            self._admit_due(steps)
            self._process_departures()
            if not self.pipeline or self.admission == "sequential":
                self._sequential_prefills_due(now)
            active = [t for t in self.tenants if not t.departed]
            order = active
            if self.qos_targets:
                order = sorted(active, key=lambda t: self._slack(t, now))
            pf_items: Dict[str, Tuple] = {}
            dec_plans: Dict[str, Tuple[Optional[KernelPlan], int]] = {}
            i = 0
            while i < len(order):
                t = order[i]
                if t.prefilling:
                    pf_items[t.tid] = self._plan_prefill_chunk(t, now)
                    i += 1
                    continue
                if not self._decodable(t, steps):
                    i += 1
                    continue
                # maximal contiguous run of decode tenants: prefill
                # planning between runs mutates pool state, so runs
                # never span a prefill tenant
                j = i
                run: List[Tenant] = []
                while (j < len(order) and not order[j].prefilling
                       and self._decodable(order[j], steps)):
                    run.append(order[j])
                    j += 1
                if not (self.batch_sched
                        and self._plan_decode_run(run, now, steps,
                                                  dec_plans)):
                    self._oracle_runs += 1
                    for g in run:
                        k = self._epoch_k(g, steps)
                        dec_plans[g.tid] = (self._schedule_epoch(g, now, k),
                                            k)
                i = j
            work: List[Tuple] = []
            seen = set()
            for t in self.tenants:
                if t.tid in seen or t.departed:
                    continue
                if t.tid in pf_items:
                    work.append(pf_items[t.tid])
                    seen.add(t.tid)
                    continue
                if t.tid not in dec_plans:
                    continue
                plan, k = dec_plans[t.tid]
                group = self._groups[t.cfg.name]
                bucketable = (
                    len(group) >= 2
                    and all(g.tid in dec_plans for g in group)
                    and all(dec_plans[g.tid] == (plan, k) for g in group)
                    and len({g.index for g in group}) == 1
                    # MoE/SSM decode plans lower to None: the plan no
                    # longer discriminates KV precision, but stacked
                    # cache pytrees must share one structure
                    and len({g.kv_dtype for g in group}) == 1)
                if bucketable:
                    work.append(("bucket", group, plan, k))
                    seen.update(g.tid for g in group)
                else:
                    self._unstack_bucket(t.cfg.name)
                    work.append(("single", t, plan, k))
                    seen.add(t.tid)
            self._clock += self.epoch_len
            if work:
                return work
            # idle gap: fast-forward to the next wake-up source (queued
            # arrival, scheduled resume, pressure-hold release, fault
            # event) — a preempted tenant must never strand the run
            wake = self._wake_steps()
            if not wake:
                return work
            self._clock = max(self._clock, min(wake))

    # ------------------------------------------------------- execution --
    def _unstack_bucket(self, name: str) -> None:
        """Materialize a held stacked-bucket cache back into its
        tenants (bucket broke, or the run is handing caches back)."""
        stacked = self._bucket_caches.pop(name, None)
        if stacked is None:
            return
        for i, g in enumerate(self._groups[name]):
            g.caches = jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)

    def _advance(self, t: Tenant, k: int) -> None:
        t.index += k
        t.tokens_served += self.batch * k
        t.epochs_served += 1
        t.run_steps += k
        if t.state == STATE_ADMITTED:
            t.state = STATE_RUNNING   # RESUMED stays visible in results
        if t.budget_left is not None:
            t.budget_left -= k

    def _kv_len(self, upto: int) -> int:
        """Static attention-read bound for decode indices < ``upto``:
        the live cache prefix rounded up to the KV window step (one MXU
        lane tile), clamped to the allocated cache.  Rounding keeps the
        number of distinct compiled shapes at max_len/LANE, and the
        window step is shared by the serial reference and the epoch
        scan so corresponding steps see identical attention shapes
        (bit-exact parity)."""
        return min(self.max_len, -(-max(1, upto) // LANE) * LANE)

    def _item_kv(self, item: Tuple) -> int:
        t0 = item[1][0] if item[0] == "bucket" else item[1]
        return self._kv_len(t0.index + item[3])

    def _fused_key(self, work: List[Tuple]) -> Tuple:
        """The fused-program cache key for an epoch's decode work: one
        (kind, arch, plan, k, kv) tuple per item.  Everything the device
        program depends on and nothing tenant-specific — the AOT warmer
        predicts these keys before their epochs exist."""
        return tuple(
            (item[0], (item[1][0].cfg.name if item[0] == "bucket"
                       else item[1].cfg.name), item[2], item[3],
             self._item_kv(item))
            for item in work)

    def _build_fused_jit(self, key: Tuple):
        """Build the fused epoch program for a work key — from the key
        ALONE (no live work items), so the AOT precompiler can build
        programs for predicted keys ahead of their first epoch."""
        cores = []
        for kind, name, plan, k, kv in key:
            if kind == "bucket":
                core = self._batched_cores.setdefault(
                    name, M.make_decode_epoch_batched(
                        self._groups[name][0].cfg))
            else:
                core = self._epoch_cores[name]
            cores.append((core, plan, k, kv))

        def fused(params_list, caches_list, token_list, index_list,
                  enc_list):
            toks_out, caches_out = [], []
            for (core, plan, k, kv), p, c, tok, idx, enc in zip(
                    cores, params_list, caches_list, token_list,
                    index_list, enc_list):
                toks, nc = core(p, c, tok, idx, enc, plan=plan, k=k,
                                kv_len=kv)
                toks_out.append(toks)
                caches_out.append(nc)
            return toks_out, caches_out

        return jax.jit(fused, donate_argnums=(1,))

    def _fused_epoch_fn(self, work: List[Tuple]) -> _CompiledEntry:
        """One jitted device program for the epoch's DECODE work: every
        decode item (single-tenant epoch scan or vmapped bucket) becomes
        an independent subgraph of a single XLA computation, so one
        dispatch replaces n_tenants calls and the CPU/TPU runtime is
        free to overlap the independent tenant subgraphs.  Jitted per
        distinct (item structure, plans, k, kv) key and cached — in
        steady state the grants repeat and every epoch is a cache hit,
        and an AOT-warmed entry dispatches a precompiled executable.
        (Prefill chunks deliberately dispatch as their own per-(arch,
        chunk, kv) jits right before this call: folding their
        run-to-run-varying shapes into the fused program would recompile
        the whole epoch on every chunk resize, whereas standalone chunk
        programs are cached across epochs AND across same-arch
        arrivals.)"""
        key = self._fused_key(work)
        entry = self._fused_jits.get(key)
        if entry is None:
            entry = _CompiledEntry(self._build_fused_jit(key))
            self._fused_jits[key] = entry
        return entry

    def compile_misses(self) -> int:
        """Total fused + prefill program builds so far — each miss is one
        program build and one XLA compile at its first call.  The --host
        benchmark gates on the post-warmup delta being zero."""
        return self._fused_jits.misses + self._prefill_jits.misses

    # ----------------------------------- AOT plan-bucket precompile -----
    def _enumerate_epoch_keys(self, steps: int) -> List[Tuple]:
        """Predicted fused-program keys for this run: walk each tenant's
        (k, kv) decode trajectory from its current position (prefill
        epochs delay the start), predict its grant plan under the current
        free pool via the pure Algorithm 1 walk, and compose per-epoch
        work keys in tenant order with the planner's bucketing predicate.
        A prediction miss costs one wasted background compile; a hit
        means the epoch boundary finds its program ready."""
        preds: Dict[str, Tuple] = {}
        for t in self.tenants:
            if t.departed:
                continue
            sims = self._simulate_block_sels(t.task, 0.0,
                                             self.cache.free_pages)
            if sims is None:
                continue
            plan = self._dec_plan(
                t, self._lower_plan(t, [(s, s.p_cur) for s in sims]))
            start, idx = 0, t.index
            if t.prompt is not None and t.token is None:
                start = -(-(t.prompt_len - t.pf_pos) // self.prefill_block)
                idx = t.prompt_len
            rem = t.budget_left if t.budget_left is not None else steps
            traj: List[Tuple[int, int]] = []
            while rem > 0 and idx < self.max_len and len(traj) < 64:
                k = min(self.epoch_len, rem, LANE - (idx % LANE))
                if idx + k > self.max_len:
                    break
                traj.append((k, self._kv_len(idx + k)))
                idx += k
                rem -= k
            preds[t.tid] = (plan, start, traj)
        horizon = max((start + len(traj)
                       for _, start, traj in preds.values()), default=0)
        keys: List[Tuple] = []
        seen = set()
        for e in range(min(horizon, 128)):
            per_tenant: Dict[str, Tuple] = {}
            for tid, (plan, start, traj) in preds.items():
                if start <= e < start + len(traj):
                    per_tenant[tid] = (plan,) + traj[e - start]
            if not per_tenant:
                continue
            key_items: List[Tuple] = []
            done = set()
            for t in self.tenants:
                if t.tid in done or t.tid not in per_tenant:
                    continue
                plan, k, kv = per_tenant[t.tid]
                group = self._groups[t.cfg.name]
                bucketable = (
                    len(group) >= 2
                    and all(g.tid in per_tenant for g in group)
                    and all(per_tenant[g.tid] == (plan, k, kv)
                            for g in group)
                    and len({g.kv_dtype for g in group}) == 1)
                if bucketable:
                    key_items.append(("bucket", t.cfg.name, plan, k, kv))
                    done.update(g.tid for g in group)
                else:
                    key_items.append(("single", t.cfg.name, plan, k, kv))
                    done.add(t.tid)
            key = tuple(key_items)
            if key and key not in seen:
                seen.add(key)
                keys.append(key)
        return keys

    def _abstract_epoch_args(self, key: Tuple) -> Optional[Tuple]:
        """Abstract (ShapeDtypeStruct) fused-program arguments for a
        predicted key — what ``jit(...).lower`` consumes.  None when an
        arch group has emptied since prediction."""
        lists: Tuple[List, ...] = ([], [], [], [], [])
        for kind, name, plan, k, kv in key:
            group = self._groups.get(name)
            if not group:
                return None
            t0 = group[0]
            specs = M.decode_epoch_input_specs(
                t0.cfg, self.batch, self.max_len, t0.kv_dtype,
                group=(len(group) if kind == "bucket" else None))
            for lst, spec in zip(lists, specs):
                lst.append(spec)
        return lists

    def warm_aot(self, steps: int) -> None:
        """Precompile the predicted fused-epoch universe on a daemon
        thread: enumerate the reachable (plans, k, kv) keys, build each
        key's program, and compile it against the predicted abstract
        arguments via ``jit(...).lower(...).compile()``.  The epoch
        boundary then dispatches precompiled executables instead of
        tracing — zero post-warmup compiles in steady state.  Restricted
        to single-device servers: pinned/sharded lowering needs concrete
        shardings the predictor does not model."""
        if not (self.pipeline and self.aot_warmup
                and self.device is None and self.mesh is None):
            return

        # The ENTIRE warmup — key enumeration, abstract-spec construction,
        # lowering, compile — runs on the daemon thread: enumeration walks
        # pure helpers (_simulate_block_sels / _lower_plan / _dec_plan)
        # and spec building traces eval_shape, both way too slow for the
        # epoch-planning path this feature exists to keep empty.  Racing
        # admissions/departures can at worst mispredict a key (one wasted
        # background compile) — the runtime path still compiles lazily on
        # any miss.
        def warm():
            try:
                keys = self._enumerate_epoch_keys(steps)
            except (AttributeError, IndexError, KeyError, RuntimeError,
                    ValueError):
                # torn read during tenancy churn (list/dict mutated under
                # the enumeration walk, or a half-departed tenant's None
                # fields): skip this warmup round.  Counted per-site so
                # out["host"] makes the swallowed path observable.
                self._aot_failed += 1
                self._aot_failed_enum += 1
                return
            for key in keys:
                try:
                    entry = self._fused_jits.peek(key)
                    if entry is None:
                        entry = _CompiledEntry(self._build_fused_jit(key))
                        self._fused_jits[key] = entry
                    specs = self._abstract_epoch_args(key)
                    if specs is None:
                        continue
                    sig = _aval_sig(specs)
                    if sig in entry.aot:
                        continue
                    entry.aot[sig] = entry.fallback.lower(*specs).compile()
                    self._aot_compiled += 1
                except (IndexError, KeyError, RuntimeError, TypeError,
                        ValueError):
                    # prediction miss (group emptied under us, stale
                    # plan, XLA lowering/compile rejection — jax wraps
                    # backend failures in Value/Type/RuntimeError):
                    # the runtime path compiles lazily on the miss
                    self._aot_failed += 1
                    self._aot_failed_compile += 1

        th = threading.Thread(target=warm, name="aot-warm", daemon=True)
        th.start()
        self._aot_threads.append(th)

    def wait_aot(self, timeout: Optional[float] = None) -> None:
        """Join outstanding AOT warmup threads (benchmarks call this
        between the warmup and measured passes)."""
        for th in self._aot_threads:
            th.join(timeout)
        self._aot_threads = [t for t in self._aot_threads if t.is_alive()]

    def _prefill_fn(self, name: str):
        """Jitted prefill-chunk program, one per arch; jit's own cache
        keys the (chunk length, kv window) variants — chunk lengths are
        align-quantized, so the variant space is tiny and reused across
        epochs and across same-arch arrivals."""
        fn = self._prefill_jits.get(name)
        if fn is None:
            fn = jax.jit(self._prefill_cores[name],
                         static_argnames=("kv_len",), donate_argnums=(1,))
            self._prefill_jits[name] = fn
        return fn

    def _dispatch_prefill(self, item: Tuple) -> Optional[Tuple]:
        """Dispatch one cache-aware prefill chunk asynchronously (the
        caches stay on device).  Returns (tenant, token) when this was
        the prompt's FINAL chunk, so the epoch dispatcher can stamp
        TTFT after the decode items have been dispatched too."""
        _, t, _, chunk = item
        kv = self._kv_len(t.pf_pos + chunk)
        fn = self._prefill_fn(t.cfg.name)
        if not t.pf_computed:
            self.spans.stamp(t.tid, "first_dispatch", clock())
        with self._on_replica():
            tok, t.caches = fn(
                t.params, t.caches,
                jnp.asarray(t.prompt[:, t.pf_pos:t.pf_pos + chunk]),
                jnp.int32(t.pf_pos), t.enc, kv_len=kv)
        t.pf_pos += chunk
        t.pf_computed += chunk
        if not t.prefilling:
            self._finish_prefill(t, tok)
            return (t, tok)
        return None

    def _dispatch_epoch(self, work: List[Tuple]) -> None:
        """Dispatch one epoch as a ``serve.dispatch`` span (the dispatch
        wall: donation backpressure makes it track device time in steady
        state), then stamp the first token of every prompt whose final
        chunk it dispatched — the blocking reads happen only after every
        one of the epoch's device calls is in flight — and log the
        epoch's counters, with the programs (fused or prefill) built
        while dispatching it."""
        m0 = self.compile_misses()
        with self.spans.span(DISPATCH):
            finished = self._dispatch_epoch_inner(work)
        built = self.compile_misses() - m0
        for t, tok in finished:
            self._stamp_ttft(t, tok)
        # (rows, k, whether the SSM state runs the in-place kernel)
        rows_k = [(self.batch * (len(it[1]) if it[0] == "bucket" else 1),
                   it[3], ssm_state_in_place(
                       (it[1][0] if it[0] == "bucket" else it[1]).cfg))
                  for it in work if it[0] != "prefill"]
        chunks = [it[3] for it in work if it[0] == "prefill"]
        self.spans.log_epoch(
            decode_items=len(rows_k),
            decode_row_steps=sum(r * k for r, k, _ in rows_k),
            ssm_inplace_row_steps=sum(r * k for r, k, ip in rows_k if ip),
            decode_steps=max((k for _, k, _ in rows_k), default=0),
            prefill_chunks=len(chunks),
            prefill_tokens=self.batch * sum(chunks),
            programs_built=built)
        self._observe_epoch()

    def _dispatch_epoch_inner(self, work: List[Tuple]) -> List[Tuple]:
        """Launch one epoch's work: the prefill chunks dispatch first
        (each through its cached per-arch chunk program), then ALL the
        decode items as ONE fused device call.  Everything is
        dispatched asynchronously and nothing here blocks on a device
        value — tokens and caches stay on device.  Returns (tenant,
        token) for every prompt whose final chunk was dispatched."""
        decode_items, finished = [], []
        for item in work:
            if item[0] == "prefill":
                done = self._dispatch_prefill(item)
                if done is not None:
                    finished.append(done)
            else:
                decode_items.append(item)
        if not decode_items:
            return finished
        fn = self._fused_epoch_fn(decode_items)
        params_list, caches_list, token_list, index_list, enc_list = (
            [], [], [], [], [])
        for item in decode_items:
            if item[0] == "bucket":
                group = item[1]
                name = group[0].cfg.name
                params_list.append(self._batched_params(name))
                stacked = self._bucket_caches.pop(name, None)
                if stacked is None:
                    stacked = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs),
                        *[g.caches for g in group])
                caches_list.append(stacked)
                token_list.append(jnp.stack([g.token for g in group]))
                index_list.append(
                    jnp.asarray([g.index for g in group], jnp.int32))
                enc_list.append(jnp.stack([g.enc for g in group])
                                if group[0].enc is not None else None)
            else:
                t = item[1]
                params_list.append(t.params)
                caches_list.append(t.caches)
                token_list.append(t.token)
                index_list.append(jnp.int32(t.index))
                enc_list.append(t.enc)
        with self._on_replica():
            toks_list, new_caches = fn(params_list, caches_list, token_list,
                                       index_list, enc_list)
        for item, toks, caches in zip(decode_items, toks_list, new_caches):
            if item[0] == "bucket":
                _, group, _, k = item
                # keep the bucket's caches STACKED for the next epoch;
                # tenants get their slices back when the bucket breaks
                self._bucket_caches[group[0].cfg.name] = caches
                for i, g in enumerate(group):
                    g.token = toks[i, :, -1:]
                    g.outputs.append(toks[i])
                    self._advance(g, k)
            else:
                _, t, _, k = item
                t.caches = caches
                t.token = toks[:, -1:]
                t.outputs.append(toks)
                self._advance(t, k)
        return finished

    def _serve_one_step(self, t: Tenant, now: float) -> None:
        """Serial reference: schedule, charge, lower, and dispatch ONE
        decode step (the pre-pipeline loop, kept as the measured
        baseline and the bit-exactness oracle)."""
        assert t.index < self.max_len, \
            f"{t.tid}: decode past max_len {self.max_len}"
        sched = self._schedule_block(t, now)
        plan = self._lower_plan(t, sched)
        t.plans.append(plan)
        dec_plan = self._dec_plan(t, plan)
        kv = self._kv_len(t.index + 1)
        if t.enc is not None:
            nxt, t.caches = t.decode(t.params, t.caches, t.token,
                                     jnp.int32(t.index), t.enc,
                                     plan=dec_plan, kv_len=kv)
        else:
            nxt, t.caches = t.decode(t.params, t.caches, t.token,
                                     jnp.int32(t.index), plan=dec_plan,
                                     kv_len=kv)
        t.token = nxt[:, None]
        t.outputs.append(nxt[:, None])
        self._advance(t, 1)

    def _resolve_qos(self, tid: str) -> Optional[float]:
        """Most-specific QoS match: the longest pattern key contained in
        the tenant id wins (a bare arch suffix must not override an
        exact tenant key).  Run ONCE per tenant at admission; the result
        is pinned on ``Tenant.qos_target``."""
        target, best_len = None, -1
        for k, v in self.qos_targets.items():
            if k in tid and len(k) > best_len:
                target, best_len = v, len(k)
        return target

    def _slack(self, t: Tenant, now: float) -> float:
        """QoS slack as a fraction of the target rate (negative = late).

        Until a tenant has completed its first epoch the slack is seeded
        AT the target (0.0): the measured ``tokens/now`` rate is
        0-or-huge near now=0 and made the ordering flap over the first
        steps.  ``now`` is computed once per epoch by the caller, not
        per tenant."""
        target = t.qos_target
        if target is None:
            return float("inf")
        if t.tokens_served == 0 or now <= 0.0:
            return 0.0
        rate = t.tokens_served / now
        want = self.batch / target
        return (rate - want) / want

    # ------------------------------------------------------------ run --
    def _begin_run(self, steps: int) -> None:
        """Per-run reset (start of :meth:`run`; the fleet driver calls
        it once per replica before interleaving their epochs)."""
        # host-path instrumentation is per-run: a warmed-server replay
        # reports its own epochs (the post-warmup compile gate)
        self.spans.reset()
        self._run_t0 = self.spans.t0
        self._run_steps = steps
        self._lookahead_adjusted = 0
        self._batched_runs = 0
        self._oracle_runs = 0
        for t in self.tenants:
            t.run_steps = 0
            if not t.outputs:
                # TTFT clock starts with the run
                t.admitted_wall = self._run_t0
                self.spans.task(t.tid, self._run_t0)
        self._run_tokens_before = sum(t.tokens_served for t in self.tenants)

    def run(self, steps: int = 16) -> Dict[str, Any]:
        self._begin_run(steps)
        t0 = self._run_t0
        if self.pipeline:
            self.warm_aot(steps)   # no-op unless aot_warmup
            pending = self._plan_epoch(0.0, steps)
            while pending:
                self._dispatch_epoch(pending)
                # one-epoch-ahead: this epoch is still executing on
                # device (async dispatch); schedule the next one now
                pending = self._plan_epoch(clock() - t0, steps)
        else:
            while True:
                now = clock() - t0   # once per round, not per tenant
                self._apply_due_faults(steps)
                self._try_resume()
                self._admit_due(steps)
                self._process_departures()
                self._sequential_prefills_due(now)
                runnable = [t for t in self.tenants
                            if self._decodable(t, steps)]
                if not runnable:
                    wake = self._wake_steps()
                    if wake:
                        self._clock = max(self._clock + 1, min(wake))
                        continue
                    break
                order = runnable
                if self.qos_targets:
                    order = sorted(runnable,
                                   key=lambda t: self._slack(t, now))
                for t in order:
                    self._serve_one_step(t, now)
                self._clock += 1
        return self._finish_run()

    def _finish_run(self) -> Dict[str, Any]:
        """Close out a run: hand bucketed caches back to their tenants,
        then fetch device values exactly once, after the last epoch (the
        ``serve.finish`` span)."""
        t0 = self._run_t0
        with self.spans.span(FINISH):
            for name in list(self._bucket_caches):
                self._unstack_bucket(name)
            live = [t.token for t in self.tenants if t.token is not None]
            if live:
                jax.block_until_ready(live)
            wall = clock() - t0
            # full decoded history [B, total_steps] per tenant, fetched
            # here (the loop itself never pulled a value)
            outputs = {t.tid: (np.concatenate(
                [np.asarray(o) for o in t.outputs], axis=-1)
                if t.outputs else np.zeros((self.batch, 0), np.int32))
                for t in self.tenants}
        served = (sum(t.tokens_served for t in self.tenants)
                  - self._run_tokens_before)
        # p95 over THIS run's admissions only (a warmed server keeps
        # departed tenants from earlier scenario replays around)
        ttfts = [t.ttft for t in self.tenants
                 if t.ttft is not None and t.admitted_wall is not None
                 and t.admitted_wall >= t0]
        return {
            "tenants": {
                t.tid: {"tokens": t.tokens_served,
                        "choices": t.choices[-4:],
                        "plans": [p.describe() for p in t.plans[-4:]],
                        "lbm_frac": (sum(c.startswith("LBM")
                                         for c in t.choices)
                                     / max(1, len(t.choices))),
                        "prompt_len": t.prompt_len,
                        "prefill_chunks": list(t.chunks),
                        "ttft_s": t.ttft,
                        "departed": t.departed,
                        "kv_wanted": t.kv_wanted,
                        "kv_reserved": t.kv_reserved,
                        "kv_dtype": t.kv_dtype,
                        "prefix_hit": t.prefix_hit,
                        "prefill_computed": t.pf_computed,
                        "state": t.state,
                        "preemptions": t.preemptions,
                        "recovery_s": list(t.recovery_s),
                        "output": outputs[t.tid]}
                for t in self.tenants
            },
            "mode": "pipelined" if self.pipeline else "serial",
            "admission": self.admission if self.pipeline else "sequential",
            "epoch_len": self.epoch_len if self.pipeline else 1,
            "replica": self.replica,
            "wall_s": wall,
            "dram_bytes": self.nec.traffic.dram_total,
            "tokens_served": served,
            "page_util": self.page_utilization(),
            "tokens_per_s": served / wall if wall > 0 else 0.0,
            "prefill_tokens": sum(t.pf_pos for t in self.tenants),
            # tokens actually prefilled ON DEVICE: the gap to
            # prefill_tokens is what prefix-hash dedup saved
            "prefill_computed": sum(t.pf_computed for t in self.tenants),
            "prefix": self.prefix.stats(),
            "p95_ttft_s": (float(np.percentile(ttfts, 95)) if ttfts
                           else None),
            "host": self._host_stats(),
            "overload": {
                "deferrals": self.deferrals,
                "shed": list(self.shed),
                "shed_count": len(self.shed),
                "queued": len(self._queue),
            },
            "faults": {
                "counts": self.fault_log.counts(),
                "log": list(self.fault_log.records),
                "preemptions": sum(t.preemptions for t in self.tenants),
                "recovery_s": [r for t in self.tenants
                               for r in t.recovery_s],
            },
        }

    def _host_stats(self) -> Dict[str, Any]:
        """Host-off-the-critical-path instrumentation for the finished
        run, all read from the span recorder: per-epoch host scheduling
        wall vs dispatch wall, compile misses per epoch, each span's
        total, each task's stamps and each epoch's counters; then the
        batched-vs-oracle planner mix and the AOT / jit-cache counters."""
        rec = self.spans
        totals = rec.totals()
        sched = totals[PLAN]["seconds"]
        dispatch = totals[DISPATCH]["seconds"]
        entries = [self._fused_jits.peek(k) for k in self._fused_jits.keys()]
        entries = [e for e in entries if isinstance(e, _CompiledEntry)]
        row_steps = sum(e["decode_row_steps"] for e in rec.epoch_log)
        return {
            "epochs": totals[DISPATCH]["count"],
            "sched_wall_s": sched,
            "dispatch_wall_s": dispatch,
            # tenant onboarding (param/cache materialization, prompt
            # synthesis) — reported apart so sched_wall is scheduling only
            "admit_wall_s": totals[ADMIT]["seconds"],
            "sched_frac": sched / dispatch if dispatch > 0 else 0.0,
            "epoch_sched_walls": [round(x, 6) for x in rec.per_epoch(PLAN)],
            "epoch_dispatch_walls": [round(x, 6)
                                     for x in rec.durations(DISPATCH)],
            "epoch_compiles": [e["programs_built"] for e in rec.epoch_log],
            "spans": totals,
            "tasks": rec.task_stamps(),
            "epoch_log": list(rec.epoch_log),
            # share of the decode row-steps whose SSM state the in-place
            # kernel updated
            "ssm_inplace_share": (sum(e["ssm_inplace_row_steps"]
                                      for e in rec.epoch_log) / row_steps
                                  if row_steps else 0.0),
            "batched_runs": self._batched_runs,
            "oracle_runs": self._oracle_runs,
            "lookahead_adjusted": self._lookahead_adjusted,
            "aot_compiled": self._aot_compiled,
            "aot_failed": self._aot_failed,
            "aot_failed_enumerate": self._aot_failed_enum,
            "aot_failed_compile": self._aot_failed_compile,
            "aot_hits": sum(e.aot_hits for e in entries),
            "fallback_calls": sum(e.fallback_calls for e in entries),
            "jit_cache": {
                "fused": {"hits": self._fused_jits.hits,
                          "misses": self._fused_jits.misses,
                          "evictions": self._fused_jits.evictions},
                "prefill": {"hits": self._prefill_jits.hits,
                            "misses": self._prefill_jits.misses,
                            "evictions": self._prefill_jits.evictions},
            },
        }


class FleetServer:
    """Multi-tenant serving over a JAX device mesh: one epoch-pipelined
    :class:`MultiTenantServer` per replica chip, each with its own
    per-chip CaMDN control stack (:class:`ReplicaAllocators` — no page
    pool, NEC ledger, or allocator profile is shared between chips),
    plus a global admission layer that routes arrivals to the
    least-loaded replica.

    * **Topology** comes from :func:`repro.launch.mesh.make_serving_mesh`
      — an ``(n_replicas, tp)`` mesh over ``('data', 'model')``.  At
      ``tp=1`` each replica is one chip and tenants are *data-sharded*
      across chips by placement: every tenant's params/caches/token are
      ``jax.device_put``-committed to its replica's device, so each
      replica's fused epoch jit executes on its own chip.  At ``tp>1``
      a replica is a tensor-parallel group: params/caches are
      device_put with the ``distributed.sharding`` specs and the model's
      ``shard_hint`` constraints activate during tracing.
    * **Routing**: load = pages granted out of the replica's pool (decode
      grants + KV reservations) + queued prefill chunks, read back from
      each replica's control stack; ties break on active tenant count,
      then replica index (identical specs round-robin).  The routed spec
      gets the GLOBAL admission index pinned as its ``seed``, so tenant
      identity (params, prompt, tid) is route-independent.
    * **Lockstep epochs**: every replica plans one epoch per fleet round
      (its logical clock advances ``epoch_len`` per round, exactly like
      a single-device run), all replicas' epochs dispatch back-to-back
      asynchronously, and the one-epoch-ahead host/device overlap now
      also overlaps host scheduling for replica *r* with device work on
      every other replica.  Idle gaps fast-forward all clocks together.
    * **Contract**: per-replica decode token streams are bit-identical
      to replaying that replica's routed scenario
      (:meth:`replica_scenarios`) on a fresh single-device server —
      asserted by tests and the ``fleet`` benchmark entry.
    """

    def __init__(self, n_replicas: Optional[int] = None, tp: int = 1,
                 mesh: Any = None, arch_ids: Optional[List[str]] = None,
                 batch: int = 2, max_len: int = 128,
                 pages_per_replica: int = VMEM_PAGES, epoch_len: int = 8,
                 tenants: Optional[List[TenantSpec]] = None,
                 arrivals: Optional[PoissonArrivals] = None,
                 prefill_chunk: int = 2 * LANE, steps_per_s: float = 1.0,
                 qos_targets: Optional[Dict[str, float]] = None,
                 prefix_dedup: bool = False, kv_dtype: str = "native",
                 faults: Optional[FaultPlan] = None):
        from repro.launch.mesh import make_serving_mesh, replica_submeshes
        if mesh is None:
            mesh = make_serving_mesh(n_replicas, tp=tp)
        self.mesh = mesh
        self.n_replicas = int(mesh.devices.shape[0])
        self.tp = int(mesh.devices.shape[1])
        self.epoch_len = max(1, int(epoch_len))
        self.steps_per_s = steps_per_s
        self.prefix_dedup = bool(prefix_dedup)
        self.registry = ReplicaAllocators(CacheConfig(
            total_bytes=pages_per_replica * PAGE_BYTES,
            num_slices=1, num_ways=1, npu_ways=1, page_bytes=PAGE_BYTES))
        subs = replica_submeshes(mesh)
        self.replicas = [
            MultiTenantServer([], batch=batch, max_len=max_len,
                              epoch_len=self.epoch_len, pipeline=True,
                              admission="interleaved",
                              prefill_chunk=prefill_chunk,
                              steps_per_s=steps_per_s,
                              qos_targets=dict(qos_targets or {}),
                              device=subs[r], replica=f"r{r}",
                              control=self.registry.get(f"r{r}"),
                              prefix_dedup=prefix_dedup,
                              kv_dtype=kv_dtype)
            for r in range(self.n_replicas)]
        self._clock = 0               # lockstep with every replica clock
        self._n_admitted = 0          # global admission index -> seeds
        # fleet-level fault injection: replica_kill is handled here
        # (failover re-routing); every other kind is forwarded to the
        # target replica's own inject() entry point
        self.faults = faults
        self.fault_log = FaultLog()
        self._dead: set = set()       # replica indices that have failed
        self._moved: List[Dict[str, Any]] = []   # failover re-routes
        self.scenario = FleetScenario(
            self.n_replicas, [[] for _ in range(self.n_replicas)])
        self._util_samples: List[List[float]] = [
            [] for _ in range(self.n_replicas)]
        self._queue: List[List] = []
        specs: List[TenantSpec] = [TenantSpec(a) for a in (arch_ids or [])]
        specs += list(tenants or [])
        if arrivals is not None:
            specs += arrivals.specs()
        specs.sort(key=lambda s: s.arrive_at)
        now = clock()
        for spec in specs:
            if spec.arrive_at <= 0.0:
                self._route(spec, now)
            else:
                step = int(math.ceil(spec.arrive_at * steps_per_s))
                self._queue.append([spec, None, step])
        self._queue.sort(key=lambda it: it[2])

    def enqueue(self, specs: List[TenantSpec]) -> None:
        """Queue arrivals relative to the CURRENT fleet clock (scenario
        replays on a warmed fleet, mirroring MultiTenantServer)."""
        for spec in sorted(specs, key=lambda s: s.arrive_at):
            step = self._clock + int(math.ceil(spec.arrive_at
                                               * self.steps_per_s))
            self._queue.append([spec, None, step])
        self._queue.sort(key=lambda it: it[2])

    # ---------------------------------------------------------- routing --
    def _match_lens(self, spec: TenantSpec) -> List[int]:
        """Prefix-affinity probe: the longest resident prefix each
        replica's per-chip PrefixIndex holds for this spec's prompt
        (0 everywhere when the spec isn't dedup-eligible).  Probes are
        side-effect-free — no hit/miss counters, no LRU perturbation."""
        none = [0] * self.n_replicas
        srv0 = self.replicas[0]
        if not (self.prefix_dedup and spec.param_seed is not None
                and spec.prompt_seed is not None and spec.prompt_len > 0):
            return none
        cfg = resolve_arch(spec.model)
        if cfg.family == "encdec":
            return none
        # session prompts are admission-index-independent (prefix_seed /
        # prompt_seed streams), so the probe prompt IS the real prompt
        prompt = _prompt_tokens(spec, 0, cfg, srv0.batch)
        cands = _prefix_candidates(prompt, spec.prompt_len,
                                   srv0._chunk_align(cfg))
        # probe under the key a fixed-precision replica registers with;
        # "auto" probes the native rung (its common admission outcome —
        # a mismatch only costs affinity, never correctness)
        return [srv.control.prefix.match_len(
                    cfg.name,
                    _params_key(spec, srv.kv_dtype
                                if srv.kv_dtype != "auto" else "native"),
                    cands)
                for srv in self.replicas]

    def _route(self, spec: TenantSpec, due_wall: Optional[float]) -> int:
        """Admit one due spec: prefer the replica already holding the
        longest matching prompt prefix (warm KV beats raw headroom —
        attaching is one on-device copy vs recomputing the prefix),
        tie-broken least-loaded, then fewest active tenants."""
        match = self._match_lens(spec)
        loads = [(-match[r], srv.load(), srv.active_count(), r)
                 for r, srv in enumerate(self.replicas)
                 if r not in self._dead]
        assert loads, "no live replica to route to"
        _, _, _, r = min(loads)
        routed = dataclasses.replace(
            spec,
            seed=self._n_admitted if spec.seed is None else spec.seed,
            arrive_at=self._clock / self.steps_per_s)
        self._n_admitted += 1
        t = self.replicas[r].admit_routed(routed, due_wall)
        self.scenario.per_replica[r].append(routed)
        self.scenario.routes.append((t.tid, r))
        return r

    def _route_due(self) -> None:
        now = clock()
        for item in self._queue:
            if item[1] is None and item[2] <= self._clock:
                item[1] = now   # TTFT clock: the request exists from here
        while self._queue and self._queue[0][2] <= self._clock:
            spec, due_wall, _ = self._queue.pop(0)
            self._route(spec, due_wall)

    # ----------------------------------------------------- fault paths --
    def kill_replica(self, r: int) -> List[str]:
        """Fail replica ``r`` at an epoch boundary: the router stops
        offering it, its live tenants' *specs* (tid-pinned via the
        global-admission seed) re-route by the normal prefix-affinity /
        least-loaded rule onto survivors, and each moved tenant
        re-prefills there — warm when the survivor's PrefixIndex still
        holds the prompt prefix, cold otherwise.  The moved tenant
        carries only its *remaining* decode budget, and its recovery
        latency is the survivor's TTFT measured from the kill instant.

        Returns the moved tids.  Killing the last live replica is
        refused (logged, not raised): with no survivor there is no
        failover story to exercise."""
        if r in self._dead:
            return []
        if len(self._dead) + 1 >= self.n_replicas:
            self.fault_log.record(self._clock, "replica_kill",
                                  target=f"r{r}", skipped="last live replica")
            return []
        self._dead.add(r)
        kill_wall = clock()
        srv = self.replicas[r]
        by_tid: Dict[str, TenantSpec] = {}
        for spec in self.scenario.per_replica[r]:
            aid = (spec.model if isinstance(spec.model, str)
                   else spec.model.name)
            by_tid[f"t{spec.seed}:{aid}"] = spec
        moved: List[str] = []
        for t in list(srv.tenants):
            if t.departed:
                continue
            left = t.budget_left       # None = unbounded resident tenant
            spec = by_tid.get(t.tid)
            # the chip is gone: reclaim the dead control stack's modeled
            # pages and the real device buffers (results survive)
            srv._depart(t)
            if spec is None or (left is not None and left <= 0):
                continue
            respec = spec if left is None else dataclasses.replace(
                spec, n_inferences=left)
            r_new = self._route(respec, kill_wall)
            moved.append(t.tid)
            self._moved.append({"tid": t.tid, "from": f"r{r}",
                                "to": f"r{r_new}", "step": self._clock})
        self.fault_log.record(self._clock, "replica_kill",
                              target=f"r{r}", moved=moved)
        return moved

    def _apply_fleet_faults(self, steps: int) -> None:
        """Consume due fault events on the FLEET clock: handle
        replica_kill here, forward everything else to the target
        replica (by "rN" target, by owning replica for a tenant-id
        preempt target, else the lowest-index live replica)."""
        if self.faults is None:
            return
        live = lambda: sorted(set(range(self.n_replicas)) - self._dead)
        for e in self.faults.due(self._clock):
            if e.kind == "replica_kill":
                tgt = e.target
                rid = (int(tgt[1:]) if tgt and tgt.startswith("r")
                       and tgt[1:].isdigit() else (live() or [None])[0])
                if rid is not None:
                    self.kill_replica(rid)
                continue
            rid = None
            if e.target and e.target.startswith("r") \
                    and e.target[1:].isdigit():
                rid = int(e.target[1:])
            elif e.target:   # tenant id: find the replica that owns it
                for i in live():
                    if any(t.tid == e.target and not t.departed
                           for t in self.replicas[i].tenants):
                        rid = i
                        break
            if rid is None:
                rid = (live() or [None])[0]
            if rid is not None and rid not in self._dead:
                self.replicas[rid].inject(e, steps)

    def replica_scenarios(self) -> List[List[TenantSpec]]:
        """The routed specs per replica (seeds pinned to the global
        admission index, arrive_at rebased to the admitting clock):
        replaying list ``r`` on a fresh single-device server reproduces
        replica ``r``'s decode streams bit-identically."""
        return [list(s) for s in self.scenario.per_replica]

    # -------------------------------------------------------------- run --
    def run(self, steps: int = 16) -> Dict[str, Any]:
        t0 = clock()
        for srv in self.replicas:
            srv._begin_run(steps)
        self._apply_fleet_faults(steps)
        self._route_due()
        pendings = [None if r in self._dead else srv._plan_epoch(0.0, steps)
                    for r, srv in enumerate(self.replicas)]
        self._clock += self.epoch_len
        while any(pendings) or self._queue:
            # dispatch every replica's epoch back-to-back, all async:
            # replica r's host scheduling overlaps device work on every
            # other replica as well as its own (one-epoch-ahead)
            for srv, p in zip(self.replicas, pendings):
                if p:
                    srv._dispatch_epoch(p)
            for r, srv in enumerate(self.replicas):
                self._util_samples[r].append(
                    0.0 if r in self._dead else srv.page_utilization())
            if not any(pendings) and self._queue:
                nxt = self._queue[0][2]
                if self.faults is not None:
                    f = self.faults.peek_step()
                    if f is not None and self._clock < f < nxt:
                        nxt = f   # a fault lands in the idle gap first
                if nxt > self._clock:   # fleet-wide idle gap: fast-forward
                    self._clock = nxt
                    for srv in self.replicas:
                        srv._clock = max(srv._clock, nxt)
            # kills land HERE — after the dispatched epoch completed,
            # before the next is planned — so every replica's tenants
            # are at an epoch boundary when their chip disappears
            self._apply_fleet_faults(steps)
            self._route_due()
            now = clock() - t0
            pendings = [None if r in self._dead
                        else srv._plan_epoch(now, steps)
                        for r, srv in enumerate(self.replicas)]
            self._clock += self.epoch_len
        results = [srv._finish_run() for srv in self.replicas]
        return self._merge(results, clock() - t0)

    def _merge(self, results: List[Dict[str, Any]],
               wall: float) -> Dict[str, Any]:
        tenants: Dict[str, Any] = {}
        replicas: List[Dict[str, Any]] = []
        ttfts: List[float] = []
        total = 0
        # dead replicas merge FIRST so a failed-over tenant's tid lands
        # on its survivor's entry (same tid on both servers: the dead
        # one's partial record, the survivor's completed one)
        order = sorted(range(self.n_replicas),
                       key=lambda r: (0 if r in self._dead else 1, r))
        for r in order:
            for tid, info in results[r]["tenants"].items():
                info = dict(info)
                info["replica"] = f"r{r}"
                tenants[tid] = info
        for r, (srv, res) in enumerate(zip(self.replicas, results)):
            total += res["tokens_served"]
            util = self._util_samples[r]
            replicas.append({
                "replica": f"r{r}",
                "dead": r in self._dead,
                "tokens_served": res["tokens_served"],
                "dram_bytes": res["dram_bytes"],
                "page_util_mean": float(np.mean(util)) if util else 0.0,
                "tenants": sorted(res["tenants"]),
            })
            ttfts += [t.ttft for t in srv.tenants
                      if t.ttft is not None and t.admitted_wall is not None
                      and t.admitted_wall >= srv._run_t0]
        # balance over SURVIVORS: a dead chip's idle pool is a fault
        # outcome, not a routing-imbalance signal
        utils = [rep["page_util_mean"] for rep in replicas
                 if not rep["dead"]]
        balance = min(utils) / max(utils) if utils and max(utils) > 0 else 1.0
        # recovery latency: survivor TTFT clocked from the kill instant
        # (admit_routed pinned due_wall = kill wall at re-route time)
        recov: Dict[str, float] = {}
        for m in self._moved:
            info = tenants.get(m["tid"])
            if info is not None and info.get("ttft_s") is not None \
                    and info["replica"] == m["to"]:
                recov[m["tid"]] = float(info["ttft_s"])
        return {
            "tenants": tenants,
            "mode": "fleet",
            "n_replicas": self.n_replicas,
            "tp": self.tp,
            "epoch_len": self.epoch_len,
            "wall_s": wall,
            "tokens_served": total,
            "tokens_per_s": total / wall if wall > 0 else 0.0,
            "dram_bytes": sum(rep["dram_bytes"] for rep in replicas),
            "p95_ttft_s": (float(np.percentile(ttfts, 95)) if ttfts
                           else None),
            "replicas": replicas,
            "routes": list(self.scenario.routes),
            "page_util_balance": balance,
            "failover": {
                "killed": sorted(f"r{r}" for r in self._dead),
                "moved": list(self._moved),
                "recovery_s": recov,
                "recovery_p95_s": (float(np.percentile(
                    list(recov.values()), 95)) if recov else None),
            },
            "faults": {
                "counts": self.fault_log.counts(),
                "log": list(self.fault_log.records),
                "replica_counts": [res["faults"]["counts"]
                                   for res in results],
            },
        }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+",
                    default=["yi-9b", "olmoe-1b-7b", "mamba2-370m"])
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--pages", type=int, default=128)
    ap.add_argument("--epoch-len", type=int, default=8,
                    help="decode steps per scheduling epoch (grant hold)")
    ap.add_argument("--serial", action="store_true",
                    help="serial reference loop (schedule+dispatch per step)")
    ap.add_argument("--arrivals", type=int, default=0,
                    help="Poisson arrivals joining mid-run with prompts")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="arrivals per logical second (steps_per_s=1)")
    ap.add_argument("--prompt-len", type=int, default=256,
                    help="prompt tokens per arriving tenant")
    ap.add_argument("--decode-budget", type=int, default=16,
                    help="decode steps an arrival serves before departing")
    ap.add_argument("--admission", choices=["interleaved", "sequential"],
                    default="interleaved")
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--kv-dtype", default="native",
                    choices=list(KV_PRECISION_LADDER) + ["auto"],
                    help="KV cache storage precision (auto: downgrade "
                         "per admission when the pool is tight)")
    ap.add_argument("--devices", type=int, default=0,
                    help="fleet mode: serve over an (N, 1) replica mesh "
                         "of N chips (on the CPU backend, N host devices)")
    ap.add_argument("--oracle-sched", action="store_true",
                    help="force the per-tenant Algorithm 1 oracle "
                         "(disable the batched epoch planner)")
    ap.add_argument("--lookahead", action="store_true",
                    help="predictive grant lookahead against next-epoch "
                         "pool pressure (changes grants)")
    ap.add_argument("--aot", action="store_true",
                    help="AOT-precompile predicted fused epoch programs "
                         "on a background thread")
    args = ap.parse_args()
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    arrivals = None
    if args.arrivals > 0:
        arrivals = PoissonArrivals(
            rate_per_s=args.arrival_rate, models=args.archs,
            n_arrivals=args.arrivals, n_inferences=args.decode_budget,
            prompt_len=args.prompt_len)
    if args.devices > 0:
        from repro.launch.env import set_host_device_count
        set_host_device_count(args.devices)
        fleet = FleetServer(n_replicas=args.devices, arch_ids=args.archs,
                            pages_per_replica=args.pages,
                            epoch_len=args.epoch_len, max_len=args.max_len,
                            arrivals=arrivals, kv_dtype=args.kv_dtype)
        out = fleet.run(args.steps)
        for rep in out["replicas"]:
            print(f"[fleet] {rep['replica']}: {rep['tokens_served']} tokens, "
                  f"page util {rep['page_util_mean'] * 100:.0f}%, "
                  f"tenants {rep['tenants']}")
        p95 = (f", p95 TTFT {out['p95_ttft_s'] * 1e3:.0f}ms"
               if out["p95_ttft_s"] is not None else "")
        print(f"[fleet] {out['n_replicas']} replicas (tp={out['tp']}): "
              f"{out['tokens_per_s']:.1f} tok/s observed, util balance "
              f"{out['page_util_balance']:.2f}{p95}")
        return
    srv = MultiTenantServer(args.archs, total_pages=args.pages,
                            epoch_len=args.epoch_len,
                            pipeline=not args.serial,
                            max_len=args.max_len,
                            arrivals=arrivals,
                            admission=args.admission,
                            kv_dtype=args.kv_dtype,
                            batch_sched=not args.oracle_sched,
                            lookahead=args.lookahead,
                            aot_warmup=args.aot)
    out = srv.run(args.steps)
    for tid, info in out["tenants"].items():
        ttft = (f", TTFT {info['ttft_s'] * 1e3:.0f}ms "
                f"(chunks {info['prefill_chunks']})"
                if info["ttft_s"] is not None else "")
        kv = ""
        if info["kv_wanted"]:
            kv = f", kv {info['kv_reserved']}/{info['kv_wanted']}p"
            if info["kv_dtype"] != "native":
                kv += f" @{info['kv_dtype']}"
            if info["kv_reserved"] < info["kv_wanted"]:
                kv += " (degraded)"
        print(f"[serve] {tid}: {info['tokens']} tokens, "
              f"LBM {info['lbm_frac'] * 100:.0f}%, recent {info['choices']}, "
              f"plans {info['plans']}{ttft}{kv}")
    p95 = (f", p95 TTFT {out['p95_ttft_s'] * 1e3:.0f}ms"
           if out["p95_ttft_s"] is not None else "")
    print(f"[serve] {out['mode']}/{out['admission']} "
          f"(K={out['epoch_len']}): {out['tokens_per_s']:.1f} tok/s total, "
          f"{out['prefill_tokens']} prompt tokens{p95}, "
          f"{out['dram_bytes'] / 2**20:.1f} MB modeled DRAM")
    host = out.get("host") or {}
    if host.get("epochs"):
        print(f"[serve] host: sched {host['sched_wall_s'] * 1e3:.1f}ms vs "
              f"dispatch {host['dispatch_wall_s'] * 1e3:.1f}ms "
              f"({host['sched_frac'] * 100:.1f}%), "
              f"{host['batched_runs']} batched / {host['oracle_runs']} "
              f"oracle runs, compiles/epoch {host['epoch_compiles']}, "
              f"aot {host['aot_compiled']} compiled "
              f"({host['aot_hits']} hits)")


if __name__ == "__main__":
    main()
