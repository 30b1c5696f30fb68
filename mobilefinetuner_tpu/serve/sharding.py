"""Serve-plane (dp, tp) mesh: tensor-parallel decode + block-diagonal banks.

The serving step's arrays are tiny on the activation side (one [S, E]
row per slot) and huge on the weight side (every layer's matrices, the
KV page pools, the stacked [k, ...] adapter bank). So
the sharded serve plane is weight-parallel, GSPMD-style: this module
PLACES the big buffers with NamedShardings and pins a handful of
`with_sharding_constraint`s at the head/hidden boundaries inside the
decode step — XLA inserts the (cheap, activation-sized) collectives,
and tools/check_compiled_contracts.py pins the census so a partitioning
regression moves a number instead of a pod bill.

Mesh layout (axes `("dp", "tp")` over the first dp*tp devices):

  dp   replicates weights and pools; the slot batch's activations are
       constrained to split over it ([S, ...] axis 0, S % dp == 0).
  tp   Megatron-style tensor parallelism:
         column-parallel (output-feature axis sharded): qkv/fc_in
           (GPT-2), q/gate/up (Gemma; k/v too when the KV heads
           divide tp) — each shard computes its own heads/hidden
           columns with NO communication;
         row-parallel (input-feature axis sharded): attn proj /
           fc_out (GPT-2), o_proj/down_proj (Gemma) — partial sums
           meet in one all-reduce per site.

Attention-head placement is decided ONCE per engine from the family's
head counts (ops/decode_attention.shard_heads is the single source of
truth, shared with the Pallas VMEM gates):

  KV % tp == 0   the page pools themselves shard on the KV-head axis
                 (serve/paged_kv.pool_partition_spec) — each tp shard
                 owns a per-shard head slice of the pool and reads
                 only its own pages;
  else, G % tp == 0   (GQA with few KV heads, e.g. Gemma-3 1B's
                 KV=1): pools replicate, the query-group axis G
                 shards — each shard attends all pages with its own
                 query groups;
  else           heads replicate entirely (the weights may still
                 shard; GSPMD re-gathers at the head reshape).

Block-diagonal adapter banks (PAPERS.md, arxiv 2510.23346): the bank's
stacked leaves are placed so each tp shard holds the block of every
adapter's factors that feeds its own weight shard —

  column-parallel target: B [k, L, r, d_out] shards on d_out. The
      bottleneck xa = x @ A is replicated (r is tiny), so the delta
      xa @ B is BORN on the shard that owns those output columns:
      zero adapter-specific collectives.
  row-parallel target: A [k, L, d_in, r] shards on d_in, matching the
      sharded input activation. The per-shard partial xa [S, r] joins
      the base matmul's existing all-reduce — the only adapter traffic
      is r columns riding a sum that was already being paid.

The factors stay mathematically DENSE (every request's outputs remain
token-identical to the single-chip engine — tests/test_serve_sharded.py
pins it); "block-diagonal" here is the PLACEMENT: the [k, ...] stack is
pre-cut along the TP axis so adapter hot-swap stays one traced
`at[slot].set` onto NamedSharding-stable buffers at any mesh shape
(AdapterBank.place re-jits the swap with out_shardings pinned — zero
retraces across tenancy changes).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from mobilefinetuner_tpu.models.family import Family, get_family
from mobilefinetuner_tpu.ops.decode_attention import shard_heads
from mobilefinetuner_tpu.serve.paged_kv import pool_partition_spec


def make_serve_mesh(dp: int, tp: int, devices: Optional[Sequence] = None
                    ) -> Mesh:
    """The serve plane's ("dp", "tp") mesh over the first dp*tp devices.
    Distinct from parallel/mesh.make_mesh's ("data", "fsdp") train axes:
    serving shards WEIGHTS over tp and replicates them over dp, the
    opposite of the train plane's fsdp axis."""
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={dp}, tp={tp}")
    devices = list(devices if devices is not None else jax.devices())
    n = dp * tp
    if len(devices) < n:
        raise ValueError(
            f"serve mesh ({dp}, {tp}) needs {n} devices, have "
            f"{len(devices)} — on CPU tests, force_host_devices(8) "
            f"must run before jax initializes")
    return Mesh(np.array(devices[:n]).reshape(dp, tp), ("dp", "tp"))


# which LoRA targets are column- vs row-parallel (mirrors the families'
# param leaf sets — models/family.py col_leaves / kv_col_leaves /
# row_leaves; lora.GPT2_TARGETS / GEMMA_TARGETS name the sites)
_COL_TARGETS = frozenset({"attn_qkv", "attn_q", "attn_k", "attn_v",
                          "mlp_fc_in", "q_proj", "gate_proj", "up_proj"})
_KV_COL_TARGETS = frozenset({"k_proj", "v_proj"})   # only when pools shard
_ROW_TARGETS = frozenset({"attn_proj", "mlp_fc_out", "o_proj",
                          "down_proj"})


@dataclasses.dataclass(frozen=True)
class ServeSharding:
    """One engine's placement decisions: the mesh plus the per-family
    head-axis choice, queried by the engine (device_put / out_shardings)
    and by the decode-step bodies (with_sharding_constraint helpers).
    Frozen: everything here is static w.r.t. the compiled programs."""

    mesh: Mesh
    dp: int
    tp: int
    family: Family
    nq: int           # query heads
    kv: int           # KV heads
    kv_shards: int    # tp when the pool's KV axis shards, else 1
    g_shards: int     # tp when the GQA group axis shards instead, else 1

    @classmethod
    def build(cls, family: str, config, dp: int, tp: int,
              devices: Optional[Sequence] = None) -> "ServeSharding":
        fam = get_family(family)
        dims = fam.dims(config)
        nq, kv = dims.nq, dims.KV
        if nq % tp:
            raise ValueError(
                f"mesh_tp={tp} does not divide the {family} query-head "
                f"count ({nq}): column-parallel attention needs "
                f"head-aligned weight shards")
        kv_local, g_local = shard_heads(kv, nq // kv, tp)
        return cls(mesh=make_serve_mesh(dp, tp, devices), dp=dp, tp=tp,
                   family=fam, nq=nq, kv=kv,
                   kv_shards=kv // kv_local,
                   g_shards=(nq // kv) // g_local)

    # ------------------------------------------------------- placement ----
    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    @property
    def repl(self) -> NamedSharding:
        """Fully-replicated placement — the host-side slot arrays
        (tok/pos/tbl/aid), prefill ids/mask, and incoming adapter trees
        must be COMMITTED here before dispatch: jit refuses to mix
        mesh-committed weights with uncommitted single-device arrays."""
        return self.named(P())

    def put_repl(self, tree):
        """device_put a host tree onto the mesh, replicated."""
        return jax.device_put(tree, self.repl)

    def pool_sharding(self) -> NamedSharding:
        """The page pools (layout and spec: serve/paged_kv)."""
        return self.named(pool_partition_spec(self.kv_shards > 1))

    def cache_sharding(self) -> NamedSharding:
        """One prefilled request's [L, KV, Ppad, D] cache (the engine's
        _prefill output, B squeezed) — KV axis matches the pool."""
        kv = "tp" if self.kv_shards > 1 else None
        return self.named(P(None, kv, None, None))

    def param_shardings(self, params):
        """NamedSharding tree for the frozen base params (the family's
        leaf sets; an axis shards only when tp divides it — indivisible
        leaves replicate, same fallback idiom as
        parallel/mesh.fsdp_spec_for)."""
        col = set(self.family.col_leaves)
        if self.kv_shards > 1:
            col |= self.family.kv_col_leaves
        row = self.family.row_leaves

        def rule(path, leaf):
            name = getattr(path[-1], "key", None) if path else None
            shape, nd = np.shape(leaf), np.ndim(leaf)
            if self.tp > 1 and name in col and shape[-1] % self.tp == 0:
                return self.named(P(*([None] * (nd - 1)), "tp"))
            if self.tp > 1 and name in row and nd >= 2 \
                    and shape[-2] % self.tp == 0:
                return self.named(P(*([None] * (nd - 2)), "tp", None))
            return self.repl

        return jax.tree_util.tree_map_with_path(rule, params)

    def bank_shardings(self, tree):
        """The block-diagonal AdapterBank placement (module docstring):
        B shards d_out at column-parallel targets, A shards d_in at
        row-parallel targets, scale (and any indivisible or unstacked
        leaf, e.g. lm_head) replicates."""
        col = set(_COL_TARGETS)
        if self.kv_shards > 1:
            col |= _KV_COL_TARGETS

        def rule(path, leaf):
            keys = [getattr(p, "key", None) for p in path]
            leaf_name = keys[-1] if keys else None
            target = keys[-2] if len(keys) >= 2 else None
            shape, nd = np.shape(leaf), np.ndim(leaf)
            if self.tp > 1 and leaf_name == "B" and target in col \
                    and shape[-1] % self.tp == 0:
                return self.named(P(*([None] * (nd - 1)), "tp"))
            if self.tp > 1 and leaf_name == "A" and target in _ROW_TARGETS \
                    and nd >= 2 and shape[-2] % self.tp == 0:
                return self.named(P(*([None] * (nd - 2)), "tp", None))
            return self.repl

        return jax.tree_util.tree_map_with_path(rule, tree)

    # ------------------------------------------- in-step constraints ------
    # Each returns its input UNCHANGED when no axis applies: a forced
    # fully-replicated constraint would fight GSPMD's propagation, so
    # "nothing to pin" means "stay out of the partitioner's way".
    def _c(self, x, spec: P):
        return jax.lax.with_sharding_constraint(x, self.named(spec))

    def _dp(self) -> Optional[str]:
        return "dp" if self.dp > 1 else None

    def slots(self, x):
        """[S, ...] slot-batch activations split over dp (the engine
        validates S % dp == 0 at build)."""
        if self.dp > 1 and x.shape[0] % self.dp == 0:
            return self._c(x, P("dp", *([None] * (x.ndim - 1))))
        return x

    def kv_rows(self, x):
        """[S, KV, D] per-token K/V rows (and GPT-2's [S, H, D] q):
        head axis matches the pool's KV sharding."""
        dp = self._dp()
        kv = "tp" if self.kv_shards > 1 else None
        if dp is None and kv is None:
            return x
        return self._c(x, P(dp, kv, *([None] * (x.ndim - 2))))

    def heads4(self, x):
        """[S, KV, G, D] grouped queries / attention context: whichever
        head axis this engine shards."""
        dp = self._dp()
        kv = "tp" if self.kv_shards > 1 else None
        g = "tp" if self.g_shards > 1 else None
        if dp is None and kv is None and g is None:
            return x
        return self._c(x, P(dp, kv, g, None))

    def hidden(self, x):
        """[S, F] MLP hidden activations, column-sharded between the
        in- and out-projections (skipped when tp doesn't divide F)."""
        if self.tp > 1 and x.shape[-1] % self.tp == 0:
            return self._c(x, P(*([None] * (x.ndim - 1)), "tp"))
        return x

    def prefill_cache(self, x):
        """[L, B, KV, P, D] collected prefill caches — pinned so the
        engine's prompt-page scatter receives pool-aligned K/V."""
        if self.kv_shards > 1:
            return self._c(x, P(None, None, "tp", None, None))
        return x
