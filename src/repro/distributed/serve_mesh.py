"""Device-mesh plumbing for sharded serving.

The serve engine runs its jitted steps under ``shard_map`` over a
``Mesh((tp, seq_shards), ("model", "seq"))``:

* ``"model"`` (tensor parallel) splits the *attention heads*: q head
  projections, k/v KV-head projections, per-head ConSmax beta/gamma, and
  the KV caches' hkv axis (contiguous or paged, quantized scale leaves
  riding their rows). Each shard runs the UNCHANGED serving code — the
  same four kernels, the same jnp fallbacks — on its local head slice.
  Head shards own DISJOINT heads, so the combine is one output-sized
  ``all_gather`` of per-head outputs (pure concatenation, bitwise exact)
  followed by the FULL o-projection applied on every shard — the o
  weight is deliberately REPLICATED, so the einsum sees operands
  bit-identical to the single-device step. (Summing per-shard
  o-projection partials — the megatron-style combine — reassociates the
  contraction and is NOT bit-identical; we measured ~5e-2 logit drift
  flipping sampled tokens on smoke models.)

* ``"seq"`` (sequence sharding) splits the *paged pool's page axis* into
  contiguous per-device blocks, so the ``long_500k`` shape's resident
  pages exceed one device's memory. The host allocator uses a block
  position map — slot page position j lives on shard
  ``min(j // ceil(max_pages_per_slot / seq_shards), seq_shards - 1)``,
  see serve/scheduler.PagePool — the engine keeps ONE global page table,
  and each shard localizes it in-step (``kernels.cache_layout.
  localize_page_table``): owned entries become local pool indices,
  foreign pages become the -1 holes the fill-bounded kernels already
  skip. A shard's per-head attention output is then the ConSmax partial
  over *its* pages — no running max, no denominator — combined by ONE
  output-sized fp32 ``psum``, the same pure addition the split-KV kernel
  already uses within one device. Under the block map a request whose
  pages fit one block sees exactly +0.0 from every foreign shard, so the
  psum returns the owner's bits unchanged: tokens are bit-identical to
  single-device serving. Requests longer than one block spill block by
  block across shards (that is the capacity point), spending bit-identity
  for those rows only — their fp32 addition order regroups per shard
  count.

Everything outside attention — embeddings, MLP/MoE, norms, the unembed,
fused sampling — is replicated, so logits and sampled tokens are
identical on every device and the engine's host loop is unchanged.

Single compiled shape per lifetime is preserved: the mesh, specs and
shard_map wrapping are fixed at engine construction; fill, tables and
banks remain step *values*.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ServeConfig
from repro.distributed.sharding import resolve_spec
from repro.models import transformer as T

MODEL_AXIS = "model"
SEQ_AXIS = "seq"


def serve_rules() -> dict:
    """Logical-axis rules for the serving mesh — deliberately NOT
    ``sharding.make_rules``: serving shards *attention only*. MLP, vocab
    and embeddings stay replicated so the per-layer residual stream (and
    the logits the fused sampler reads) is identical on every device and
    the attention psum is the only collective on the step."""
    return {
        # parameters: head-sharded attention, everything else replicated
        "heads": [(MODEL_AXIS,)],
        "kv_heads": [(MODEL_AXIS,)],
        # activations / caches
        "act_heads": [(MODEL_AXIS,)],
        "act_kv_heads": [(MODEL_AXIS,)],
        "act_kv_pages": [(SEQ_AXIS,)],
    }


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Everything the engine needs to build sharded jitted steps."""
    mesh: Mesh
    cfg: ModelConfig              # the global model config
    cfg_local: ModelConfig        # per-shard view (n_heads/tp, n_kv_heads/tp)
    tp: int
    seq_shards: int
    pages_per_shard: int          # paged pools: P // seq_shards (else 0)

    @property
    def psum_axes(self) -> tuple:
        return (MODEL_AXIS, SEQ_AXIS)

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def named(self, spec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ------------------------------------------------------------ specs ----
    def spec_tree(self, tree, axes_tree):
        """(array tree, logical-axes tree) -> PartitionSpec tree under the
        serve rules. Anything the rules don't name is replicated."""
        rules = serve_rules()
        return jax.tree.map(
            lambda a, ax: resolve_spec(a.shape, ax, self.mesh, rules),
            tree, axes_tree)

    def sharding_tree(self, tree, axes_tree):
        """Same, as a NamedSharding tree (for device_put placement)."""
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.spec_tree(tree, axes_tree))

    def param_specs(self, params):
        axes = T.lm_axes(self.cfg)
        specs = self.spec_tree(params, axes)

        # The o-projection is REPLICATED, not head-sharded: the combine
        # all_gathers full-head outputs and every shard applies the full
        # matmul, which is what makes the tensor-parallel step
        # bit-identical to single-device (see the module docstring).
        def fix(spec, ax):
            if (isinstance(ax, str)
                    and ax.split(",")[-3:] == ["heads", "", "embed"]):
                return P()
            return spec

        return jax.tree.map(fix, specs, axes)

    def cache_specs(self, caches, *, paged: bool, quantized: bool):
        axes = T.cache_axes(self.cfg, quantized=quantized, paged=paged)
        return self.spec_tree(caches, axes)

    # ---------------------------------------------------------- wrapping ----
    def wrap(self, fn, in_specs, out_specs):
        """shard_map ``fn`` over the plan's mesh. ``check_vma=False``:
        the bodies contain Pallas launches and data-dependent gathers
        whose replication the checker cannot infer."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def put(self, tree, shardings):
        return jax.device_put(tree, shardings)


def plan_mesh(cfg: ModelConfig, scfg: ServeConfig):
    """Build the serving MeshPlan, or None when tp * seq_shards == 1
    (single-device: no shard_map, no collectives — the engine's original
    code paths, bit for bit)."""
    tp, ns = scfg.tp, scfg.seq_shards
    if tp * ns == 1:
        return None
    n_dev = jax.device_count()
    if n_dev < tp * ns:
        raise ValueError(
            f"serve mesh ({tp} x {ns}) needs {tp * ns} devices, have "
            f"{n_dev}. On CPU, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp * ns} "
            "(before jax initializes) to split the host into that many "
            "devices.")
    if cfg.score_norm != "consmax":
        raise ValueError(
            f"sharded serving requires score_norm='consmax' (got "
            f"{cfg.score_norm!r} for {cfg.arch_id}): per-shard partials "
            "combine by pure addition only when the normalizer has no "
            "running max or denominator — softmax/softermax would need a "
            "cross-shard log-sum-exp exchange this path does not implement")
    if tp > 1 and (cfg.n_heads % tp or cfg.n_kv_heads % tp):
        raise ValueError(
            f"tp={tp} must divide n_heads ({cfg.n_heads}) and "
            f"n_kv_heads ({cfg.n_kv_heads}) for {cfg.arch_id} — heads "
            "shard in equal slices (the GQA group ratio is preserved "
            "when both divide)")
    pages_per_shard = 0
    if ns > 1:
        # ServeConfig.__post_init__ already enforced paged_kv, fill_bound
        # and page divisibility; recompute the per-shard block here
        pages_per_shard = scfg.num_pages // ns
    elif scfg.paged_kv:
        pages_per_shard = scfg.num_pages
    devices = np.asarray(jax.devices()[: tp * ns]).reshape(tp, ns)
    mesh = Mesh(devices, (MODEL_AXIS, SEQ_AXIS))
    # the per-shard view the step bodies run under: head counts divided,
    # head_dim PINNED (cfg.head_dim_ falls back to d_model // n_heads,
    # which would silently grow when n_heads shrinks)
    cfg_local = cfg.replace(n_heads=cfg.n_heads // tp,
                            n_kv_heads=cfg.n_kv_heads // tp,
                            head_dim=cfg.head_dim_)
    return MeshPlan(mesh=mesh, cfg=cfg, cfg_local=cfg_local, tp=tp,
                    seq_shards=ns, pages_per_shard=pages_per_shard)
