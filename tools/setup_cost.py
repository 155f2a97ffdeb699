"""What a trainer program costs the host BEFORE the compile cache is asked:
tracing it and lowering it, off the chip, for a described v5e.

    python -m tools.setup_cost [--model kimi_linear_48b_a3b] [--tokens 8192]
    python -m tools.setup_cost --model olmoe_1b_7b --tokens 4096 --fsdp 4

A warm start pays this for every program it runs (the cache's key is the
lowered module), and a Pallas kernel is lowered to its Mosaic module in
Python at every place its call occurs in the lowered text, cache hit or
miss. One JSON line a program (``grad_step``, ``logp_forward``: the
engine's own builders, gradient checkpointing on, one packed row of
``--tokens``; ``--fsdp n``: a row a chip on the first n of the described
host's four, the parameters laid out as the engine lays them): seconds to
trace, seconds to lower, the ``tpu_custom_call``s of the lowered text by
kernel name, the text's size (``--compile``: and the compiled program's bytes
a device, with the collectives the compiler put in by name). The seconds are this
sandbox's CPU's and compare one tree with another on the same machine; no
number here is a device's.

This is the ESTIMATE made before a chip call. The measurement is the chip
host's own: every benchmark run logs each program's seconds to trace, to
lower, to compile and to load (``utils/goodput.CompileTracker``:
``program_table()``, the traced line's ``setup_trace_s.train`` and its
five siblings), and reads two to four times this tool's seconds.

``jax.default_backend()`` is the CPU here, so the choices the ops make from
the platform (``ops/kda.scan_impl``, ``ops/moe.grouped_impl``, the splash
kernel, ...) and from the devices the process holds
(``ops/rotary.rotate_impl``) are steered to the cell's for the length of the
script.
"""

import argparse
import collections
import json
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import areal_tpu  # noqa: E402,F401 — the scoped-VMEM flag, before the backend

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

_KERNEL = re.compile(r'kernel_name\s*=\s*"([^"]+)"')


def custom_calls(text: str) -> dict:
    """The ``tpu_custom_call``s of a lowered module's text by kernel name:
    each is one lowering of that kernel's body in Python."""
    names = collections.Counter()
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = _KERNEL.search(line)
        names[m.group(1) if m else "?"] += 1
    return dict(sorted(names.items()))


_COLLECTIVE = re.compile(
    r"= (\S+) (all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)


def compiled_cost(lowered) -> dict:
    """What the described chip's compiler makes of a lowered program: the
    bytes a device (arguments, temporaries) and its collectives, by kind and
    result shape, with how often each occurs."""
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    found = collections.Counter(
        f"{kind} {shape}" for shape, kind in
        _COLLECTIVE.findall(compiled.as_text())
    )
    return {
        "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
        "collectives": dict(sorted(found.items())),
    }


def program_cost(jitted, *args, compile: bool = False) -> dict:
    t0 = time.perf_counter()
    traced = jitted.trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    text = lowered.as_text()
    return {
        "trace_s": round(t1 - t0, 2),
        "lower_s": round(t2 - t1, 2),
        "custom_calls": custom_calls(text),
        "text_bytes": len(text),
        **(compiled_cost(lowered) if compile else {}),
    }


def trainer_programs(
    mc, mesh, tokens: int, attend, rows: int = 1, param_dtype=None,
) -> dict:
    """``{name: (jitted program, abstract arguments)}``: the engine's
    ``grad_step`` (gradient checkpointing on, the GRPO loss) and
    ``logp_forward`` for ``rows`` packed rows of ``tokens`` on ``mesh``;
    the parameters in ``param_dtype`` (None: bf16; the cells' engines hold
    float32, which is what a reading of memory needs)."""
    from areal_tpu.engine import programs
    from areal_tpu.models.transformer import init_params
    from areal_tpu.ops.functional import token_logprobs
    from areal_tpu.parallel import feasibility

    from areal_tpu.models.transformer import param_logical_axes
    from areal_tpu.parallel import sharding as sharding_lib

    def sds(tree, dtype=None, shardings=None):
        if shardings is None:
            shardings = jax.tree_util.tree_map(lambda s: s.sharding, tree)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(
                s.shape, dtype or s.dtype, sharding=sh
            ),
            tree, shardings,
        )

    shapes = jax.eval_shape(lambda: init_params(mc, jax.random.PRNGKey(0)))
    # as the engine lays a model and its rows out
    params = sds(shapes, param_dtype, sharding_lib.tree_shardings(
        mesh, param_logical_axes(mc)
    ))
    rows_sh = sharding_lib.batch_sharding(mesh)
    row = jax.ShapeDtypeStruct((rows, tokens), jnp.int32)
    arrays = {
        **{k: row for k in
           ("tokens", "segment_ids", "positions", "t_loss_mask")},
        **{k: jax.ShapeDtypeStruct(row.shape, jnp.float32) for k in
           ("t_logprobs", "t_prox_logp", "t_advantages")},
        "s_rewards": jax.ShapeDtypeStruct((rows, 8), jnp.float32),
    }
    if mc.block_diffusion is not None:  # such a policy's rows carry three more
        arrays.update(dict.fromkeys(
            ("t_bd_stream", "t_bd_labels", "t_bd_scored"), row
        ))
    arrays = sds(arrays, shardings={k: rows_sh for k in arrays})
    return {
        "grad_step": (
            programs.build_grad_step(
                mc, mesh, jnp.bfloat16, attend, remat=True,
                loss_fn=feasibility._grpo_loss_fn,
                loss_weight_fn=feasibility._grpo_loss_weight_fn,
            ),
            (params, sds(params, jnp.float32), arrays),
        ),
        "logp_forward": (
            programs.build_logp_forward(
                mc, mesh, jnp.bfloat16, attend, hook=token_logprobs
            ),
            (params, arrays),
        ),
    }


def cell_programs(model: str, tokens: int, fsdp: int = 1, param_dtype=None,
                  devices=None) -> dict:
    """``trainer_programs`` of ``feasibility.MODEL_CONFIGS[model]`` on the
    first ``fsdp`` of ``devices``, a row of ``tokens`` each (a cell's
    programs are one ``grad_step`` and one ``logp_forward`` a row length,
    whatever its sequences), the attention a TPU resolves to. ``devices``
    None: a described ``v5e:2x2`` host's, with the ops' platform rules
    steered to a TPU's for the process's life."""
    from areal_tpu.api.cli_args import ParallelismConfig
    from areal_tpu.ops.ring_attention import attention_fn
    from areal_tpu.parallel import feasibility
    from areal_tpu.parallel import mesh as mesh_lib

    if devices is None:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
        jax.default_backend = lambda: "tpu"  # the ops' platform rules
        # the devices the cell's process holds (``ops/rotary.rotate_impl``)
        jax.device_count = lambda: fsdp
    mc = feasibility.MODEL_CONFIGS[model]()
    mesh = mesh_lib.make_mesh(
        ParallelismConfig(fsdp_parallel_size=fsdp), devices[:fsdp]
    )
    from areal_tpu.models.transformer import seq_parallel_refusal

    attend = attention_fn(
        seq_parallel_refusal(mc)[0] or "flash", mesh,
        block_cap=2048,  # what the chip's probe settles on
        topk=mc.dsa_topk if mc.has_indexer else 0,
    )
    return trainer_programs(
        mc, mesh, tokens, attend, rows=fsdp, param_dtype=param_dtype
    )


def main(argv=None):
    from areal_tpu.parallel import feasibility

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="kimi_linear_48b_a3b",
                    choices=sorted(feasibility.MODEL_CONFIGS))
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--fsdp", type=int, default=1, choices=(1, 2, 4),
                    help="chips of the described host, a row of --tokens each")
    ap.add_argument("--param-dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--compile", action="store_true",
                    help="compile too: bytes a device, collectives by name")
    args = ap.parse_args(argv)

    for name, (jitted, a) in cell_programs(
        args.model, args.tokens, args.fsdp, jnp.dtype(args.param_dtype)
    ).items():
        print(json.dumps({
            "model": args.model, "tokens": args.tokens, "program": name,
            "chips": args.fsdp,
            **program_cost(jitted, *a, compile=args.compile),
        }), flush=True)


if __name__ == "__main__":
    main()
