"""Static contract checks for the serving Pallas kernels — grids and
BlockSpecs introspected *without running the kernels*.

``capture_launches`` monkeypatches ``pl.pallas_call`` with a recorder: the
kernel wrapper functions run exactly as written (block-size selection,
GQA folding, grid clamping), but the Pallas launch itself is replaced by a
stub that records the resolved grid, dimension semantics, per-operand block
shapes/dtypes/memory spaces, scratch allocation, and scalar-prefetch
operands, then returns zeros of the declared out_shape. Nothing compiles,
nothing executes — the checks below run on any backend in milliseconds.

Checks (the kernel half of the serving contract):

* ``vmem-budget`` — per-program VMEM working-set estimate:
  ``2 x (input blocks + output blocks) + scratch`` (the factor 2 is
  Mosaic's double-buffered pipeline), each block counted in its tiled
  layout (``cache_layout.tiled_bytes``), against a per-core budget, plus a
  per-operand block cap — the class of bug the decode kernel's
  ``_fold_factor`` 2 MB K/V cap exists to prevent, caught at analysis time
  instead of as a Mosaic OOM on hardware.
* ``parallel-write-race`` — a grid dimension marked ``parallel`` whose
  programs map to the *same* output block is a write race: two programs
  race on one buffer. The serving kernels' all-parallel grids are legal
  precisely because every parallel dim reaches the output index map (each
  KV shard owns a partial slot — the split-KV pure-addition invariant);
  a reduction axis that does not reach the output must be ``arbitrary``
  (the paged-prefill VMEM accumulator). Evaluated by probing each output
  index map at unit program-id offsets.
* ``grid-semantics-declared`` — a serving kernel must declare
  ``dimension_semantics`` for its grid; an undeclared grid silently
  serializes (and hides races from this checker).
* ``scalar-prefetch`` — the paged kernels' scalar-prefetch operands (page
  table, index, kv_len, layer) must match the declared arity and be int32:
  SMEM scalars drive BlockSpec index maps, and a float or wide-int table is
  a mis-wired launch that Mosaic reports only at compile time on hardware.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.jaxpr_lint import Finding
from repro.kernels import cache_layout as CL

VMEM_BUDGET_BYTES = 16 << 20     # per-core VMEM on current TPU generations
BLOCK_CAP_BYTES = 2 << 20        # per-operand block cap (decode _fold_factor
                                 # keeps K/V blocks double-bufferable)


@dataclass
class BlockInfo:
    """One operand's blocking: shape of the per-program block, its dtype,
    byte size, and the BlockSpec index map (kept callable for probing)."""
    block_shape: tuple
    dtype: str
    nbytes: int
    memory_space: str            # "smem" | "vmem" | "any"
    index_map: object = None

    def to_json(self) -> dict:
        return {"block_shape": list(self.block_shape), "dtype": self.dtype,
                "bytes": self.nbytes, "memory_space": self.memory_space}


@dataclass
class KernelLaunch:
    """Everything recorded about one ``pl.pallas_call`` launch."""
    name: str
    grid: tuple
    dimension_semantics: tuple | None
    in_blocks: list = field(default_factory=list)
    out_blocks: list = field(default_factory=list)
    scratch_bytes: int = 0
    num_scalar_prefetch: int = 0
    scalar_avals: list = field(default_factory=list)   # (shape, dtype) pairs
    scalar_operands: list = field(default_factory=list)  # np copies, for maps
    n_operands: int = 0
    n_specs: int = 0

    def vmem_working_set(self) -> int:
        """Double-buffered pipeline estimate: 2 x (in + out) + scratch."""
        blocks = [b for b in self.in_blocks + self.out_blocks
                  if b.memory_space == "vmem"]
        return 2 * sum(b.nbytes for b in blocks) + self.scratch_bytes

    def to_json(self) -> dict:
        return {
            "name": self.name, "grid": [int(g) for g in self.grid],
            "dimension_semantics": (list(self.dimension_semantics)
                                    if self.dimension_semantics else None),
            "in_blocks": [b.to_json() for b in self.in_blocks],
            "out_blocks": [b.to_json() for b in self.out_blocks],
            "scratch_bytes": self.scratch_bytes,
            "num_scalar_prefetch": self.num_scalar_prefetch,
            "scalar_avals": [[list(s), d] for s, d in self.scalar_avals],
            "vmem_working_set_bytes": self.vmem_working_set(),
        }


def _mem_space(spec) -> str:
    """"smem", "vmem", or "any": an operand left where it lives (HBM) for
    the kernel's own DMAs, which holds no VMEM block."""
    ms = str(getattr(spec, "memory_space", None)).lower()
    if "smem" in ms:
        return "smem"
    return "any" if "any" in ms or "hbm" in ms else "vmem"


def _dim_semantics(compiler_params):
    if compiler_params is None:
        return None
    if isinstance(compiler_params, dict):          # {"mosaic": {...}} form
        inner = compiler_params.get("mosaic", compiler_params)
        ds = (inner.get("dimension_semantics")
              if isinstance(inner, dict) else None)
    else:
        ds = getattr(compiler_params, "dimension_semantics", None)
    return tuple(ds) if ds is not None else None


def _block_info(spec, shape, dtype, index_map_default=None) -> BlockInfo:
    bshape = tuple(getattr(spec, "block_shape", None) or shape)
    bshape = tuple(int(d) for d in bshape if d is not None)
    space = _mem_space(spec)
    nbytes = (int(np.prod(bshape, dtype=np.int64)) * np.dtype(dtype).itemsize
              if space == "smem" else CL.tiled_bytes(bshape, dtype))
    return BlockInfo(bshape, str(np.dtype(dtype)), nbytes, space,
                     getattr(spec, "index_map", index_map_default))


def _scratch_bytes(scratch_shapes) -> int:
    """VMEM the scratch buffers take (semaphores and SMEM take none)."""
    total = 0
    for s in jax.tree_util.tree_leaves(scratch_shapes):
        dt = getattr(s, "dtype", None)
        if dt is not None and "vmem" in str(getattr(s, "memory_space", "")):
            total += CL.tiled_bytes(tuple(getattr(s, "shape", ())), dt)
    return total


@contextlib.contextmanager
def capture_launches():
    """Patch ``pl.pallas_call`` so kernel wrappers record their launches
    instead of executing them. Yields the list the records land in; each
    recorded launch's stub returns zeros of the declared ``out_shape``, so
    wrapper code after the launch (partial sums, reshapes) still runs."""
    launches: list[KernelLaunch] = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, out_shape=None, *, grid_spec=None, grid=(),
                         in_specs=None, out_specs=None, scratch_shapes=(),
                         compiler_params=None, **_kw):
        if grid_spec is not None:
            grid = grid_spec.grid
            in_specs = grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = (getattr(grid_spec, "scratch_shapes", ())
                              or scratch_shapes)
            n_prefetch = int(getattr(grid_spec, "num_scalar_prefetch", 0))
        else:
            n_prefetch = 0
        in_specs = list(in_specs or [])
        out_list = (list(out_shape) if isinstance(out_shape, (tuple, list))
                    else [out_shape])
        out_spec_list = (list(out_specs) if isinstance(out_specs,
                                                       (tuple, list))
                         else [out_specs] * len(out_list))

        def run(*operands):
            launch = KernelLaunch(
                name=getattr(kernel, "__name__", None) or getattr(
                    getattr(kernel, "func", None), "__name__", "<kernel>"),
                grid=tuple(int(g) for g in grid),
                dimension_semantics=_dim_semantics(compiler_params),
                scratch_bytes=_scratch_bytes(scratch_shapes),
                num_scalar_prefetch=n_prefetch,
                n_operands=len(operands), n_specs=len(in_specs))
            scalars = operands[:n_prefetch]
            blocked = operands[n_prefetch:]
            launch.scalar_avals = [(tuple(s.shape), str(s.dtype))
                                   for s in scalars]
            launch.scalar_operands = [np.asarray(s) for s in scalars]
            for spec, op in zip(in_specs, blocked):
                launch.in_blocks.append(_block_info(spec, op.shape, op.dtype))
            for spec, out in zip(out_spec_list, out_list):
                launch.out_blocks.append(
                    _block_info(spec, out.shape, out.dtype))
            launches.append(launch)
            zeros = [jnp.zeros(o.shape, o.dtype) for o in out_list]
            return (type(out_shape)(zeros)
                    if isinstance(out_shape, (tuple, list)) else zeros[0])

        return run

    pl.pallas_call = fake_pallas_call
    try:
        yield launches
    finally:
        pl.pallas_call = real


# --------------------------------------------------------------- checks ----
def _probe_index_map(index_map, ids, launch):
    """Evaluate a BlockSpec index map at concrete program ids; scalar-ref
    index maps (PrefetchScalarGridSpec) get the captured scalar operands as
    numpy refs."""
    try:
        out = index_map(*ids)
    except TypeError:
        out = index_map(*ids, *launch.scalar_operands)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(np.asarray(x)) for x in out)


def check_write_races(launch: KernelLaunch) -> list[Finding]:
    """A ``parallel`` grid dim whose programs map to the same output block
    races. Probe each output index map at program id 0...0 and at a unit
    offset along every parallel dim of size >= 2: identical block indices
    mean two concurrent programs write one buffer. ``arbitrary`` dims are
    exempt — they are sequential, the accumulate-in-scratch pattern."""
    findings = []
    sem = launch.dimension_semantics
    if sem is None:
        return findings
    base_ids = [0] * len(launch.grid)
    for oi, block in enumerate(launch.out_blocks):
        if block.index_map is None:
            continue
        base = _probe_index_map(block.index_map, base_ids, launch)
        for dim, (size, kind) in enumerate(zip(launch.grid, sem)):
            if kind != "parallel" or size < 2:
                continue
            ids = list(base_ids)
            ids[dim] = 1
            if _probe_index_map(block.index_map, ids, launch) == base:
                findings.append(Finding(
                    "parallel-write-race", launch.name,
                    f"grid dim {dim} (size {size}) is 'parallel' but does "
                    f"not reach output {oi}'s block index — two programs "
                    "write the same block; mark the dim 'arbitrary' or give "
                    "each program its own output slot (the split-KV "
                    "partials invariant)", (dim, int(size), oi)))
    return findings


def check_grid_semantics(launch: KernelLaunch) -> list[Finding]:
    if launch.grid and launch.dimension_semantics is None:
        return [Finding("grid-semantics-declared", launch.name,
                        f"grid {launch.grid} launched without "
                        "dimension_semantics — the kernel neither promises "
                        "parallelism nor admits sequencing",
                        (tuple(int(g) for g in launch.grid),))]
    if (launch.dimension_semantics is not None
            and len(launch.dimension_semantics) != len(launch.grid)):
        return [Finding("grid-semantics-declared", launch.name,
                        f"dimension_semantics arity "
                        f"{len(launch.dimension_semantics)} != grid rank "
                        f"{len(launch.grid)}",
                        (len(launch.dimension_semantics),
                         len(launch.grid)))]
    return []


def check_vmem(launch: KernelLaunch, *,
               budget_bytes: int = VMEM_BUDGET_BYTES,
               block_cap_bytes: int = BLOCK_CAP_BYTES) -> list[Finding]:
    findings = []
    for kind, blocks in (("input", launch.in_blocks),
                         ("output", launch.out_blocks)):
        for i, b in enumerate(blocks):
            if b.memory_space == "vmem" and b.nbytes > block_cap_bytes:
                findings.append(Finding(
                    "vmem-budget", launch.name,
                    f"{kind} block {i} {b.block_shape} {b.dtype} is "
                    f"{b.nbytes} bytes > per-block cap {block_cap_bytes} — "
                    "not double-bufferable (the _fold_factor class of bug)",
                    (kind, i, b.block_shape, b.nbytes)))
    ws = launch.vmem_working_set()
    if ws > budget_bytes:
        findings.append(Finding(
            "vmem-budget", launch.name,
            f"per-program VMEM working set ~{ws} bytes "
            f"(2x(in+out) + scratch) exceeds the {budget_bytes}-byte "
            "budget", (ws, budget_bytes)))
    return findings


def check_scalar_prefetch(launch: KernelLaunch) -> list[Finding]:
    findings = []
    if launch.num_scalar_prefetch == 0:
        return findings
    expected = launch.num_scalar_prefetch + launch.n_specs
    if launch.n_operands != expected:
        findings.append(Finding(
            "scalar-prefetch", launch.name,
            f"launch passes {launch.n_operands} operands but declares "
            f"{launch.num_scalar_prefetch} scalar-prefetch + "
            f"{launch.n_specs} blocked specs (= {expected})",
            (launch.n_operands, expected)))
    for i, (shape, dtype) in enumerate(launch.scalar_avals):
        if np.dtype(dtype) != np.dtype(np.int32):
            findings.append(Finding(
                "scalar-prefetch", launch.name,
                f"scalar-prefetch operand {i} {shape} is {dtype}, not "
                "int32 — SMEM scalars driving index maps must be int32",
                (i, shape, str(dtype))))
    return findings


KERNEL_CHECKS = (check_grid_semantics, check_write_races, check_vmem,
                 check_scalar_prefetch)

CHECK_CATALOG = {
    "grid-semantics-declared": "every launched grid declares "
                               "dimension_semantics",
    "parallel-write-race": "every 'parallel' grid dim reaches each output "
                           "block index (disjoint writes)",
    "vmem-budget": "per-program working set 2x(in+out)+scratch under the "
                   "VMEM budget; every block under the double-buffer cap",
    "scalar-prefetch": "scalar-prefetch arity matches the operands and "
                       "scalars are int32",
}


def check_launch(launch: KernelLaunch, **kw) -> list[Finding]:
    """Run every kernel contract check against one captured launch."""
    findings = []
    for check in KERNEL_CHECKS:
        findings.extend(check(launch, **kw) if check is check_vmem
                        else check(launch))
    return findings


# --------------------------------------- the four serving kernels' specs ----
def serving_launches(cfg, scfg) -> dict[str, KernelLaunch]:
    """Capture the decode + prefill kernel launches for one serve config at
    its real shapes (full fill — the capacity grid, the worst case for VMEM
    and races), without running them. Contiguous or paged follows
    ``scfg.paged_kv``; block sizes follow the config's kv-block knobs,
    mirroring exactly what ``make_serve_fns`` would launch: the paged
    kernels get every layer's pools, stacked, and read the last layer."""
    from repro.kernels.consmax_decode.kernel import (consmax_decode,
                                                     consmax_decode_paged)
    from repro.kernels.consmax_prefill.kernel import (consmax_prefill,
                                                      consmax_prefill_paged)
    H, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    b, L, c = scfg.max_slots, scfg.max_seq, scfg.prefill_chunk
    beta = jnp.linspace(0.5, 2.5, H)
    gamma = jnp.full((H,), 100.0)
    window = cfg.window
    softcap = cfg.attn_softcap
    out: dict[str, KernelLaunch] = {}

    def grab(label, caught):
        assert len(caught) == 1, (label, len(caught))
        launch = caught[0]
        launch.name = label
        out[label] = launch

    kv_dtype = CL.kv_cache_dtype(scfg.kv_cache_dtype)
    quant = CL.kv_quantized(kv_dtype)
    if scfg.paged_kv:
        ps, P = scfg.page_size, scfg.num_pages
        npg = scfg.max_pages_per_slot
        n = cfg.n_super_layers
        layer = jnp.asarray(n - 1, jnp.int32)
        pool = jnp.zeros((n, P, ps, hkv, d), kv_dtype)
        spool = jnp.ones((n, P, ps, hkv), jnp.float32) if quant else None
        table = (jnp.arange(b * npg, dtype=jnp.int32) % P).reshape(b, npg)
        with capture_launches() as caught:
            consmax_decode_paged(
                jnp.zeros((b, H, d)), pool, pool, layer, table,
                jnp.full((b,), L, jnp.int32), beta, gamma, window=window,
                softcap=softcap, fill_bound=scfg.fill_bound,
                k_scale=spool, v_scale=spool)
        grab("decode_paged", caught)
        with capture_launches() as caught:
            consmax_prefill_paged(
                jnp.zeros((1, c, H, d)), pool, pool, layer, table[:1],
                jnp.full((1,), L - c, jnp.int32),
                jnp.full((1,), c, jnp.int32), beta, gamma, window=window,
                softcap=softcap, fill_bound=scfg.fill_bound,
                k_scale=spool, v_scale=spool)
        grab("prefill_paged", caught)
    else:
        cache = jnp.zeros((b, L, hkv, d), kv_dtype)
        scale = jnp.ones((b, L, hkv), jnp.float32) if quant else None
        with capture_launches() as caught:
            consmax_decode(
                jnp.zeros((b, H, d)), cache, cache,
                jnp.full((b,), L, jnp.int32), beta, gamma, window=window,
                softcap=softcap, bk=scfg.decode_kv_block,
                fill_bound=scfg.fill_bound, k_scale=scale, v_scale=scale)
        grab("decode_contiguous", caught)
        slot = jnp.zeros((1, L, hkv, d), kv_dtype)
        sslot = jnp.ones((1, L, hkv), jnp.float32) if quant else None
        with capture_launches() as caught:
            consmax_prefill(
                jnp.zeros((1, c, H, d)), slot, slot,
                jnp.full((1,), L - c, jnp.int32),
                jnp.full((1,), c, jnp.int32), beta, gamma, window=window,
                softcap=softcap, bk=scfg.prefill_kv_block,
                fill_bound=scfg.fill_bound, k_scale=sslot, v_scale=sslot)
        grab("prefill_contiguous", caught)
    return out
