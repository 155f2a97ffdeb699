"""Flash (splash) attention wrapper for packed segment streams on TPU.

Role of the reference's flash-attn varlen path (realhf/impl/model/modules/
attn.py wraps flash_attn_varlen_func; areal relies on HF flash-attention-2):
on TPU the analog is the Pallas splash-attention kernel family shipped with
JAX (jax.experimental.pallas.ops.tpu.splash_attention) — fused streaming
softmax, O(T) activation memory, differentiable (custom VJP), with native
segment-id support that matches our packed layout exactly.

This wrapper adapts splash's [H, T, D] MQA-grouped convention to the
framework's [B, T, H, D] packed-stream convention and masks padding
(segment id 0) on the way out. It is the trainer's attention core on a TPU
(``resolve_attn_impl`` makes ``attn_impl="auto"`` this kernel there); the XLA
``segment_attention`` is the plain form it is held equal to, the CPU's path,
and what a row shorter than one tile takes. The kernel keeps float32 scores,
softmax and accumulation, as the XLA form does. On the CPU it runs only in
Pallas interpret mode (``interpret=True``: ``tests/test_flash_parity.py``).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.ops import band_attention, bd_attention
from areal_tpu.ops.basic import segment_attention
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _sk,
    splash_attention_mask as _sm,
)

# what a block edge must be a multiple of, and a row a whole number of
_LANES = 128

# below this edge the per-grid-step overhead dominates long-context
# attention; a TPU that cannot compile it is misconfigured, not slow
_MIN_PROBED_EDGE = 512


def _tiles(inst: "Instance") -> "_sk.BlockSizes":
    """The splash kernel's blocks of ``inst`` (``instance``'s): ONE edge for
    every tile of forward and backward (the rows' tile mask is laid over them
    all at that edge), ``inst.kvc`` the compute tile of both, and one
    backward kernel for dq, dk and dv (``inst.fused``) or the two split
    ones."""
    edge, kvc = inst.edge, inst.kvc
    dq = {} if inst.fused else dict(block_q_dq=edge, block_kv_dq=edge)
    return _sk.BlockSizes(
        block_q=edge, block_kv=edge, block_kv_compute=kvc,
        block_q_dkv=edge, block_kv_dkv=edge, block_kv_dkv_compute=kvc,
        use_fused_bwd_kernel=inst.fused, **dq,
    )


# A window-attention layer's band (the one local mask there is: ``instance``)
# WHERE IT COMES HERE: on a TPU a band of 128-wide heads has kernels of its
# own (``_strip_band``, at the file's end). Its tile edge, and the SPLIT
# backward: the fused one writes a partial dq for every
# kv block, [T / edge, heads, T, d], over a grid it does not shrink to the
# band: at T = 16384, edge 512 and 64/8 heads of 128 that is 8.6 GB a layer,
# most of it zeros (the compiler's memory_analysis() of grad_step read 14.9
# GB of temporaries with it and 7.6 GB without); the split kernels iterate the
# band's blocks only and write dq once. Measured on the v5e with
# ``tools/microbench_attn_v2.py --layer-window 512`` on the cell's packed
# rows, 64/8 heads of 128, forward + backward ms a layer (PERF.md, PR 39):
# T = 16384: edge 512 split 25.7, edge 512 fused 63.6, edge 256 split 37.5,
# edge 256 fused refused (16.6 GB); T = 8192: 12.0, 19.8, 16.8, 44.9.
# ``block_kv_compute`` 512 (256: 26.9 and 12.6). A band of 1024 keys, 32/4
# heads of 128 on its cell's rows (``--layer-window 1024``, PERF.md, PR 45):
# T = 32768: edge 512 split 34.5, edge 1024 split 36.5, edge 1024 fused
# 61.2, edge 512 fused refused (17.8 GB); T = 16384: 16.4, 17.9, 22.1, 26.0.
BAND_EDGE = 512


@functools.lru_cache(maxsize=None)
def probe_block_size(max_block: int = 2048, probe_t: int = 2048) -> int:
    """Find the largest splash block edge this backend can actually run.

    The large edges need the scoped-VMEM limit raised
    (LIBTPU_INIT_ARGS=--xla_tpu_scoped_vmem_limit_kib=65536, appended by
    ``areal_tpu/__init__`` when it runs before jax backend init; without it
    the 2048 edge is refused and 1024 compiles). What the limit allows is
    PROBED: compile+run a small fwd+bwd at each candidate edge and keep the
    largest that works; the caller hands it on as ``block_cap`` and
    ``instance`` never chooses above it. 0 on the CPU backend, where the
    kernel runs only in Pallas interpret mode: nothing to cap. On a TPU, if
    no edge >= 512 compiles the probe RAISES with the compiler's message
    — an entry point that lost the flag must not go on to train on tiles a
    quarter the size. The result is a VALUE: memoised here (a failure is
    not), read by no other function of this module; call once from engine
    init (TPU backends only — never inside a trace).
    """
    if jax.default_backend() == "cpu":
        return 0
    import logging

    log = logging.getLogger("areal_tpu.flash")
    q = jnp.ones((4, probe_t, 128), jnp.bfloat16)
    k = jnp.ones((probe_t, 128), jnp.bfloat16)
    mask = _sm.MultiHeadMask([_sm.CausalMask((probe_t, probe_t))] * 4)
    b = max_block
    refused = None
    while b >= _MIN_PROBED_EDGE:
        try:
            kernel = _sk.make_splash_mqa_single_device(
                mask, block_sizes=_tiles(Instance("splash", 0, b, True, 512))
            )
            out = jax.grad(
                lambda q_: kernel(q_, k, k).astype(jnp.float32).sum()
            )(q)
            jax.block_until_ready(out)
            log.info("splash block edge probed: %d", b)
            return b
        except Exception as e:  # noqa: BLE001 — mosaic raises various types
            log.warning(
                "splash block %d unavailable (%s: %s) — trying smaller",
                b, type(e).__name__, str(e)[:200],
            )
            refused = e
            b //= 2
    raise RuntimeError(
        f"no splash block edge in [{_MIN_PROBED_EDGE}, {max_block}] "
        "compiles on this TPU (is --xla_tpu_scoped_vmem_limit_kib in "
        "LIBTPU_INIT_ARGS — was areal_tpu imported before the jax backend "
        f"started?). The compiler said: {type(refused).__name__}: {refused}"
    ) from refused


def takes_head_dim(d: int, dv: "int | None" = None) -> bool:
    """Head dimensions the trainer hands to the kernel: multiples of 64.
    64 (half a lane row) and 128 have trained on the v5e (Qwen2-0.5B and
    -1.5B, PERF.md PR 22 and PR 26), 256 (PR 52), and a query/key width of
    192 over a value width ``dv`` of 128 (latent attention: one layer at
    8,192-token rows since PR 33, six cores a step at 16,384 since PR 54;
    ``instance`` gives the two wide shapes tiles of their own since PR 57);
    Mosaic compiles others too, but none has run on a chip: they stay on
    XLA."""
    dv = d if dv is None else dv
    return d > 0 and d % 64 == 0 and (dv == d or (d, dv) == (192, 128))


def resolve_attn_impl(
    attn_impl: str, seq_parallel_size: int, head_dims: "tuple[int, int]",
    forced: str = "", no_seq_parallel: str = "",
) -> str:
    """A train engine's ``attn_impl`` against what the process can observe
    (the way ``GenerationEngine`` resolves its own ``auto``) and what the
    model declares (``models.transformer.seq_parallel_refusal``): ``forced``,
    the one attention it has whatever was asked, and ``no_seq_parallel``, why
    it has no sequence-parallel form (a ``str.format`` template over
    ``attn_impl`` and ``seq_parallel_size``), raised where one was asked for.
    On a TPU without sequence parallelism ``auto`` is the fused splash
    kernel wherever it takes the model's ``head_dims``
    (``ModelConfig.attn_head_dims``): the XLA form writes float32 scores
    [heads, T, T] per layer and pass. Elsewhere ``auto`` is that XLA form,
    which GSPMD shards; so is an explicit SP kernel with nothing to split."""
    no_sp = seq_parallel_size == 1
    # a forced attention is whatever was asked: only a split is refused
    asked_sp = not no_sp or (not forced and attn_impl in ("ring", "ulysses"))
    if no_seq_parallel and asked_sp:
        raise ValueError(no_seq_parallel.format(
            attn_impl=attn_impl, seq_parallel_size=seq_parallel_size
        ))
    if forced:
        return forced
    if attn_impl == "auto":
        fused = (
            jax.default_backend() == "tpu" and no_sp
            and takes_head_dim(*head_dims)
        )
        return "flash" if fused else "xla"
    if attn_impl in ("ring", "ulysses") and no_sp:
        return "xla"
    return attn_impl


class Instance(NamedTuple):
    """Which attention a row runs (``instance``): ``kind`` "" the XLA form's
    row | "strip" the band's own kernels | "splash" | "bd" a block-diffusion
    row's own kernels (``edge`` their query block, ``kvc`` their key block,
    ``ops/bd_attention.py``); ``back``, the keys behind
    a query its static mask keeps beside the query's own (0: causal);
    ``edge``, the tile edge (the strip kernels' query block; 0 under XLA);
    ``fused``, the one backward kernel of the splash or the "bd" kernels, or
    their two; ``kvc``, the splash kernel's ``block_kv_compute`` (0 where
    another kernel runs)."""

    kind: str
    back: int
    edge: int
    fused: bool
    kvc: int

    @property
    def backward(self) -> str:
        """"fused" | "split" | "strip", "" for a row the XLA form takes:
        what a trainer's grad dispatch span says of a core."""
        if self.kind not in ("splash", "bd"):
            return self.kind
        return "fused" if self.fused else "split"


def instance(
    t: int, d: int, layer_window: int = 0, block_cap: int = 0,
    interpret: bool = False, *, heads: int, block_diffusion: bool = False,
) -> Instance:
    """THE rule: which kernel instance a row of ``t`` tokens with ``heads``
    query heads ``d`` wide runs in a layer whose window is ``layer_window``
    keys, the query's own among them (0: a full-attention layer), under
    ``block_cap``, the largest edge the backend ran (``probe_block_size``; 0:
    uncapped). ``interpret``: Pallas interpret mode, the CPU's tests. Every
    threshold of this file is here. A layer's window reaches
    ``layer_window - 1`` keys back and is the one local mask there is: a
    band, unless it reaches over the whole row (plain causal). A row's own
    sequences need no mask of their own: the rows' run-time tile mask
    (``tiles_needed``) drops every tile no sequence needs.

    Measured on the v5e with ``tools/microbench_attn_v2.py`` on the cells'
    own packed rows under the rows' tile mask (``_row_kernel``: an empty tile
    is free, so a smaller edge drops more and costs more a tile), forward +
    backward ms a layer, the fused backward, ``block_kv_compute`` 512
    (PERF.md, PR 42, has the whole table; in brackets the same kernel
    without the tile mask, which at today's edge is PR 41's):

    ====== =============== ============= ============= =============
    T      heads           edge 512      edge 1024     edge 2048
    ====== =============== ============= ============= =============
    2048   14/2 of 64      0.511 (0.548) 0.559 (0.562)
    2048   16/16 of 128    0.772 (0.797) 0.767 (0.762)
    4096   14/2 of 64      1.732 (2.045) 1.637 (1.857)
    4096   16/16 of 128    2.694 (2.956) 2.272 (2.451)
    8192   32/32, 192|128                21.42 (26.95) 26.71 (30.67)
    8192   48/8 of 128                   18.91 (23.33) 20.46 (23.12)
    8192   8/2 of 128                    3.517 (3.894) 3.679 (3.824)
    16384  48/8 of 128                   61.85 (88.40) 58.93 (80.76)
    16384  8/2 of 128                    8.131 (14.49) 7.843 (13.14)
    ====== =============== ============= ============= =============

    Heads wider than 128 on the rows of the cells that have them (PERF.md,
    PR 57, has every compute tile; "a / b": the fused / the SPLIT backward;
    the last column edge 2048 under ``block_kv_compute`` 1024):

    ====== =============== ============= ============= ============= ====
    T      heads           edge 512      edge 1024     edge 2048     1024
    ====== =============== ============= ============= ============= ====
    2048   32/32, 192|128  2.620 / 3.112 2.473 / 3.045 3.116 / 3.408 3.07
    2048   16/2 of 256     1.347 / 1.463 1.352 / 1.553 1.694 / 1.918 1.71
    4096   32/32, 192|128  8.150 / 9.153 7.573 / 8.743 9.561 / 10.05 8.49
    4096   16/2 of 256     4.162 / 4.925 4.028 / 4.883 5.374 / 5.761 5.44
    8192   32/32, 192|128  26.77 / 29.06 22.73 / 25.25 27.76 / 28.15 24.2
    8192   16/2 of 256     14.33 / 15.92 12.24 / 14.15 15.83 / 16.20 16.0
    16384  32/32, 192|128  78.79 / 80.79 63.61 / 67.28 73.88 / 73.36 64.1
    16384  16/2 of 256     41.91 / 46.05 35.71 / 39.20 43.45 / 43.49 44.0
    ====== =============== ============= ============= ============= ====

    - so for heads to 128 wide edge 512 to T = 2048 (dense heads +8.6%, the
      16 of 128 -0.7%), 1024 to T = 8192, 2048 above; WIDER heads take 1024
      at every rung (-13.9% and -17.8% at 16,384, where the rule for 128 had
      them at 2048 until PR 57; -2.2 to -5.6% at 2,048 for 192 | 128, Kimi's
      rows and JoyAI's, and nothing for 256), with the fused backward where
      its partial dq fits (next item). JoyAI's at 1024 is 3 GiB and does
      not fit; heads of 192, a lane tile and a half, then take edge 2048
      under a compute tile of 1024, which their fused dkv kernel wants there
      (64.13 against 73.88 at 512 and 75.90 at 2048; Kimi's rows at 8,192:
      23.30 against 26.71; heads of 256 gain nothing from it, 44.02 against
      43.45) and which beats 1024 with the split kernels (67.28); heads of
      256 that do not fit would take 1024 split (39.20 against 43.45);
    - the FUSED dq+dkv backward wherever its partial dq a layer,
      [T / edge, heads, T, d] in bf16, is within 2.5 GiB: one pass over the
      data, 10-25% under the split kernels everywhere (PR 26; the tables
      above), but ``grad_step`` holds that array beside the state. What the
      cells run: 1.5 GiB fits (Laguna's 48 heads of 128 and JoyAI's 32 of
      192 at 16,384 and edge 2048: JoyAI's ``grad_step`` then reads 16.18 of
      the 17.18 GB its chip gives, PERF.md, PR 54), 2 GiB fits (Qwen3-Next's
      16 heads of 256 at 16,384 and edge 1024), 3 GiB (JoyAI at 1024) and
      4 GiB (Mellum, below) do not;
    - a band that comes to the splash kernel at all (not 128-wide heads on a
      TPU: ``band_attention.strip_block``) takes ``BAND_EDGE`` and the split
      backward (above; PERF.md, PR 39 and PR 45, have the sweeps at 64/8 and
      32/4 heads of 128; the rows' tile mask is worth 1.7% there, 25.28
      against 25.72 ms);
    - so at T = 32768 and 32 heads of 128 the SPLIT backward: the partial
      dq is 4.3 GB a layer at edge 2048, and ``grad_step`` of the cell that
      has such rows then reads 16.8 GB with its moments (arguments 2.72 +
      temporaries 11.34 + 2.72; the chip gives a program 16.9) against 14.4
      GB split.
      Forward + backward ms a layer on that cell's rows, 32/4 heads of 128
      (PERF.md, PR 45): edge 2048 fused 134.4, split 158.7; edge 1024 fused
      151.9, split 173.1: so edge 2048 stays, and the split costs 18% of the
      full layer's core there;
    - ``block_kv_compute`` 512 rather than the edge (the smaller compute
      tile overlaps the kv DMA; 256 loses by 1-4% at 192 | 128 and is even
      at 256; at edge 1024 a compute tile of 1024 is within 0.8% of 512 at
      every width), but for the one case above.

    ``block_diffusion``: the row holds a clean stream and its masked copies
    under the mask that is causal between blocks and two-way inside one
    (``ops/bd_attention.py``: no static mask of the splash kernel says it,
    and a noisy query's clean keys lie a stream away, so the row takes
    kernels of its own wherever it is whole tiles of theirs, heads 128 wide;
    else the XLA form). Measured on the v5e on the block-diffusion cell's own
    packed rows of 16,384 slots, 32 / 4 heads of 128 (my chip run, PR 58,
    ``chiprun_in/bd_sweep.py``; real slots and sequences a row; forward |
    forward + backward ms a layer, by query x key slots a tile; the backward
    is the SPLIT pair dq + dkv, the one form there was then):

    ================ ============== ============== ==============
    tile             15,648, 1 seq  15,328, 1 seq  15,852, 2 seqs
    ================ ============== ============== ==============
    128 x 512        10.82 | 35.84  10.06 | 33.24  10.51 | 34.39
    256 x 256         9.94 | 34.38   9.13 | 31.53  10.06 | 34.47
    256 x 512         9.34 | 32.01   8.55 | 29.16   9.53 | 32.01
    512 x 256         9.30 | 32.93   8.70 | 30.50   9.39 | 32.94
    512 x 512         8.91 | 30.84   8.22 | 28.43   9.63 | 32.94
    256 x 1024        9.81 | 33.32   9.00 | 30.41  10.03 | 33.27
    512 x 1024        9.74 | 33.35   9.15 | 31.21  10.59 | 35.47
    ================ ============== ============== ==============

    so 256 x 512 (``bd_attention.BQ``, ``BK``): within 3.7% of the best on
    every row; 512 x 512 wins a one-sequence row by 2.5-3.6% and loses a
    two-sequence row by 2.9% (a larger tile keeps more of the border tiles
    that hold few pairs: 244 of 1,024 tiles kept where 256 x 512 keeps 461
    of 2,048), and a pass's rows hold one to three sequences.

    The backward as ONE kernel (``bd_attn_bwd``: a kept tile's scores formed
    once, five products where the pair makes seven, a key head's dK and dV
    resident in VMEM) beside the pair on the same rows (my chip run, PR 59,
    the same script; BACKWARD ms a layer, forward + backward less forward;
    "pair / one"; both give the same bits):

    ================ ============== ============== ==============
    tile             15,648, 1 seq  15,328, 1 seq  15,852, 2 seqs
    ================ ============== ============== ==============
    256 x 256            - / 16.49      - / 14.99      - / 16.36
    256 x 512        22.63 / 15.60  20.57 / 14.17  22.45 / 15.51
    512 x 256            - / 16.55      - / 15.48      - / 16.60
    512 x 512        22.31 / 15.72  20.17 / 14.52  23.23 / 16.88
    256 x 1024       23.47 / 16.54  21.37 / 15.09  23.19 / 16.43
    512 x 1024       23.56 / 64.90  22.07 / 63.98  24.86 / 64.65
    ================ ============== ============== ==============

    so the one kernel at the forward's own tile, 256 x 512 (-31% of the
    backward on every row; 512 x 512 is within 1% on a one-sequence row and
    9% behind on the two-sequence one; at 512 x 1024 a tile's float32 scores
    are 16 MiB each and the kernel spills): one tile table serves both. Making
    the query block's operands once a walk instead of once a tile read
    15.80 / 14.35 / 15.64 at 256 x 512, no gain, and is not kept. The one
    kernel holds ``bd_attention.bwd_resident_bytes`` in VMEM, 2 x [T, 128]
    float32 of scratch and 2 x [T, 128] bf16 of output blocks a key head: 12
    MiB at 8,192 slots, 24 MiB at 16,384, 48 MiB at 32,768, and its call
    states that and 32 MiB of working room as its ``vmem_limit_bytes`` (the
    v5e has 128 MiB; the compiler takes the kernel with 16 MiB of room and
    refuses it with 12). Rows to 32,768 slots take it (the same sequences
    packed into 32,768-slot rows, same call: 49.13 / 34.06, 48.77 / 33.80 and
    41.27 / 28.55 ms; the tree as committed read 15.57, 14.11 and 15.43 at
    16,384); a longer row keeps the pair.

    The edge is never above ``block_cap``, always a divisor of ``t``."""
    if block_diffusion:
        bq, bk = bd_attention.BQ, bd_attention.BK
        fits = d == _LANES and t % bq == 0 and t % bk == 0
        on_chip = interpret or jax.default_backend() == "tpu"
        if fits and on_chip:
            # the one backward kernel where a key head's dK and dV, float32
            # scratch and bf16 output blocks, are within 48 MiB of VMEM
            resident = bd_attention.bwd_resident_bytes(t, d, 2)
            return Instance("bd", 0, bq, resident <= 48 << 20, bk)
        return Instance("", 0, 0, False, 0)
    if t % _LANES:
        # not whole tiles of 128 tokens: short, its quadratic scores small
        return Instance("", 0, 0, False, 0)
    back = layer_window - 1 if 0 < layer_window - 1 < t else 0
    wide = d > _LANES  # 192 | 128 and 256
    odd = wide and d % _LANES != 0  # a lane tile and a half: 192 | 128

    def fits(edge: int) -> bool:
        # the fused backward's partial dq a layer, bf16, within 2.5 GiB
        return t // edge * heads * t * d * 2 <= 5 << 29

    if back:
        if interpret or jax.default_backend() == "tpu":
            # the band's own kernels (``ops/band_attention.py``) where they
            # take the shape: heads a multiple of 128 wide, a row of blocks
            bq = band_attention.strip_block(t, back, d)
            if bq:
                return Instance("strip", back, bq, False, 0)
        want = BAND_EDGE
    elif wide:
        want = 2048 if odd and not fits(1024) and fits(2048) else 1024
    else:
        want = 512 if t <= 2048 else 1024 if t <= 8192 else 2048
    want = min(want, block_cap or want)
    edge = _LANES
    while edge * 2 <= min(want, t) and t % (edge * 2) == 0:
        edge *= 2
    kvc = 1024 if odd and edge == 2048 else min(512, edge)
    return Instance("splash", back, edge, not back and fits(edge), kvc)


def backward_form(
    t: int, d: int, layer_window: int = 0, block_cap: int = 0, *, heads: int
) -> str:
    """``Instance.backward`` of the instance a row of ``t`` tokens gets in a
    layer of ``layer_window``: the splash kernel's one backward kernel or
    its two, or the band's own."""
    return instance(t, d, layer_window, block_cap, heads=heads).backward


@functools.lru_cache(maxsize=32)
def _make_kernel(t: int, rep: int, inst: Instance, interpret: bool):
    """The splash kernel of ``inst`` (``instance``'s) on a row of ``t``
    tokens, ``rep`` query heads a key head."""
    # ensure_compile_time_eval: this may be reached inside a jit trace, but
    # the kernel object (and the mask arrays it processes) must be concrete —
    # it is cached across traces, and a tracer captured here would escape.
    with jax.ensure_compile_time_eval():
        if inst.back:
            # a window-attention layer's band: ``back`` = its keys less the
            # query's own (511, 1023), under the run-time segment ids
            head = _sm.LocalMask((t, t), (inst.back, 0), 0)
        else:
            head = _sm.CausalMask((t, t))
        mask = _sm.MultiHeadMask([head for _ in range(rep)])
        # residual_checkpoint_name marks out+logsumexp so a remat policy
        # saving "attn_out" skips the forward-kernel recompute in the
        # backward (what models/transformer.apply's remat saves)
        return _sk.make_splash_mqa_single_device(
            mask, block_sizes=_tiles(inst),
            residual_checkpoint_name="attn_out", interpret=interpret,
        )


def tiles_needed(segment_ids, edge: int, xp=jnp):
    """The TILE rule: which square tiles of ``edge`` tokens a packed row's
    causal attention needs, ``[..., T / edge, T / edge]`` bool (query block,
    key block) from ``segment_ids`` ``[..., T]``. With ``lo[b]`` the smallest
    non-zero id in block ``b`` and ``hi[b]`` the largest, a tile under the
    diagonal is needed only if ``hi[j] >= lo[i]``: else no sequence with a key
    in ``j`` still runs in ``i`` (ids rise along a packed row,
    ``pack_batch_rows``; padding is 0, at the tail; a row whose ids do not
    rise keeps tiles it could drop, never the reverse). A DIAGONAL tile is
    always needed, whatever it holds: every query keeps its own key, so no
    softmax sees zero keys, and a query block of padding alone reads
    padding's keys, as it does without the rule. A comparison of block
    summaries, never a ``[T, T]`` mask; ``xp`` is ``jnp`` for the kernel's
    mask and ``numpy`` for the engine's count of the same tiles."""
    s = segment_ids.reshape(*segment_ids.shape[:-1], -1, edge)
    hi = s.max(-1)
    lo = xp.where(s > 0, s, xp.iinfo(xp.int32).max).min(-1)
    n = hi.shape[-1]
    shared = hi[..., None, :] >= lo[..., :, None]  # [.., query, key block]
    return xp.tril(shared, -1) | xp.eye(n, dtype=bool)


def tile_counts(
    segment_ids, d: int, block_cap: int = 0, *, heads: int
) -> "tuple[int, int]":
    """What the engine counts a dispatch: the tiles on and under the
    diagonal of packed rows ``segment_ids`` [rows, T] (numpy, on the host)
    at the edge ``instance`` gives such a row in a full-attention layer of
    ``heads`` query heads ``d`` wide under ``block_cap``, and those of them
    the kernel computes: the ones a sequence of the row needs. (0, 0) for a
    row the XLA form takes."""
    t = segment_ids.shape[-1]
    inst = instance(t, d, block_cap=block_cap, heads=heads)
    if not inst.kind:
        return 0, 0
    n = t // inst.edge
    kept = tiles_needed(segment_ids, inst.edge, np)
    return len(segment_ids) * n * (n + 1) // 2, int(kept.sum())


def _lay_over(info, needed, kv_major: bool):
    """The static ``MaskInfo`` of one kernel with the tiles a row does not
    need (``needed`` [q blocks, kv blocks], ``tiles_needed``) turned off in
    ``block_mask``: what the kernel reads from scalar memory AT RUN TIME to
    decide whether a grid step computes. ``data_next`` names the block each
    step works on: the kv block of the forward and dq kernels' step (row
    = query block), the query block of the dkv kernel's (``kv_major``:
    column = kv block); a local mask's grid is shrunk to its band, so the
    other coordinate is read from there and not from the position. A step
    that is off gets the block of the next step that is on, in the order the
    grid is walked, as the static info gives the steps above the diagonal:
    the pipeline then fetches nothing for it, and the next block it needs is
    already on its way."""
    static = info.block_mask  # [1, rows, cols]: one mask for every head
    there = info.data_next.astype(jnp.int32)
    _, rows, cols = static.shape
    if kv_major:
        qi, kj = there, jnp.arange(cols)[None, None, :]
    else:
        qi, kj = jnp.arange(rows)[None, :, None], there
    block_mask = jnp.where(needed[qi, kj], static, 0)
    # the walk: the last grid axis fastest (kv blocks of a query block for
    # the forward and dq, query blocks of a kv block for dkv)
    walk = (lambda a: a.swapaxes(1, 2)) if kv_major else (lambda a: a)
    on, blocks = walk(block_mask > 0), walk(there)
    at = jnp.where(on, jnp.arange(on.size).reshape(on.shape), on.size - 1)
    nxt = jax.lax.cummin(at.reshape(-1), reverse=True)
    data_next = walk(blocks.reshape(-1)[nxt].reshape(on.shape))
    return info._replace(
        block_mask=block_mask.astype(static.dtype),
        data_next=data_next.astype(info.data_next.dtype),
    )


@jax.jit
def _row_kernel(kernel, seg_row):
    """``kernel`` (``_make_kernel``'s, static: causal or a layer's band) for
    ONE row: the same ``kwargs``, with the tiles that no
    sequence of ``seg_row`` [T] needs laid over its static tile masks, the
    forward's and the backward's alike (``tiles_needed``, at the one edge
    ``_tiles`` gives them all). It removes only pairs the run-time segment
    ids mask to -inf anyway, so the result is the static kernel's: to the
    bit where a sequence's own tiles are all kept, which is always (their
    order too). The mask infos are traced leaves of the kernel pytree, a few
    small XLA ops in front of the call; under a ``vmap`` over rows they are
    batched (Pallas then loops over the rows; one row a device is the
    trainer's case and has no loop). Jitted, so that a program which meets
    the call site again (each layer kind, forward, recomputed and backward,
    each row length) traces these ops once and carries them as one equation
    (``tools.setup_cost`` reads what a program costs to trace)."""
    needed = tiles_needed(seg_row, kernel.kwargs["block_sizes"].block_q)
    dq = kernel.dq_mask_info  # the split backward's (a layer's band)
    return _sk.SplashAttentionKernel(
        _lay_over(kernel.fwd_mask_info, needed, False),
        None if dq is None else _lay_over(dq, needed, False),
        _lay_over(kernel.dkv_mask_info, needed, True),
        **kernel.kwargs,
    )


def flash_segment_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, T]
    causal: bool = True,
    interpret: bool = False,  # Pallas interpret mode: the CPU's tests
    # the largest tile edge the backend ran (``probe_block_size``, handed
    # down by the engine through ``attention_fn``); 0 = uncapped
    block_cap: int = 0,
    # a window-attention LAYER's: a query sees its ``layer_window`` latest
    # keys, its own among them (0 = no window). Shorter than the sequences:
    # it changes the result, and is the model's, not the engine's
    layer_window: int = 0,
) -> jnp.ndarray:
    """Drop-in replacement for ops.basic.segment_attention on TPU. ``v``
    may be narrower than ``q`` and ``k`` (latent attention's 192 | 128): the
    kernel takes the value width from ``v``, nothing is padded. Which kernel
    instance the rows run is ``instance``'s to say."""
    assert causal, "splash path is causal-only (decoder models)"
    t, heads, d = q.shape[1:]
    inst = instance(t, d, layer_window, block_cap, interpret, heads=heads)
    if not inst.kind:
        return segment_attention(
            q, k, v, segment_ids, causal=True, window=layer_window
        )
    run = _strip_band if inst.kind == "strip" else _splash
    return run(q, k, v, segment_ids, inst, interpret)


def flash_bd_attention(
    q: jnp.ndarray,  # [B, T, Hq, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [B, T]
    blocks: jnp.ndarray,  # [B, T]: a slot's position over the block length
    streams: jnp.ndarray,  # [B, T]: 0 the clean stream, 1..K a noisy one
    interpret: bool = False,
    block_cap: int = 0,
) -> jnp.ndarray:
    """A block-diffusion policy's training rows through the instance
    ``instance`` gives them: the kernels of ``ops/bd_attention.py`` on a TPU
    (q, k, v as the projections made them, a reshape either side), the plain
    XLA form under the same mask elsewhere."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    inst = instance(
        t, d, 0, block_cap, interpret, heads=hq, block_diffusion=True
    )
    if not inst.kind:
        return bd_attention.bd_segment_attention(
            q, k, v, segment_ids, blocks, streams
        )
    return bd_attention.bd_attention(
        q.reshape(b, t, hq * d), k.reshape(b, t, hkv * d),
        v.reshape(b, t, hkv * d), segment_ids, blocks, streams, (hq, hkv),
        inst.edge, inst.kvc, inst.fused, interpret,
    ).reshape(q.shape)


def _splash(q, k, v, segment_ids, inst: Instance, interpret: bool):
    """The splash kernel of ``inst`` on packed rows, under each row's own
    tile mask: [B, T, H, D] to the kernel's [H, T, D] grouped by key head
    and back, the queries scaled, padding's rows 0."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    kernel = _make_kernel(t, rep, inst, interpret)
    scale = d**-0.5
    qg = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.transpose(0, 2, 1, 3).reshape(b, hkv, rep, t, d)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    def per_batch(q_, k_, v_, seg_row):
        ids = _sk.SegmentIds(q=seg_row, kv=seg_row)
        row = _row_kernel(kernel, seg_row)
        return jax.vmap(row, in_axes=(0, 0, 0, None))(q_, k_, v_, ids)

    out = jax.vmap(per_batch)(qg, kt, vt, segment_ids)
    out = out.reshape(b, hq, t, v.shape[-1]).transpose(0, 2, 1, 3)
    valid = (segment_ids > 0)[:, :, None, None]
    return jnp.where(valid, out, 0).astype(q.dtype)


def _strip_band(q, k, v, segment_ids, inst: Instance, interpret: bool):
    """A layer's band ``inst.back`` keys behind a query through its own
    kernels (``instance`` said "strip"): q, k, v as the projections made
    them, [T, heads x D], a reshape either side; ``out`` and logsumexp named
    ``attn_out`` there as the splash kernel names its own."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    return band_attention.band_attention(
        q.reshape(b, t, hq * d), k.reshape(b, t, hkv * d),
        v.reshape(b, t, hkv * d), segment_ids, (hq, hkv), inst.back,
        inst.edge, interpret,
    ).reshape(q.shape)
