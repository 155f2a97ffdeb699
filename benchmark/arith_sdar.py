"""The yardstick's arithmetic for SDAR-30B-A3B-Chat trained as a block-diffusion
policy, as one chip of eight holds it: operations of a TRAINED token when a
row holds each sequence's clean stream and a masked copy a denoising state,
and what the new kernels are asked for.

As ``benchmark/arith.py``: kept with the benchmark so that no later PR can
change how a number is worked out, written from a configuration file's own
keys (``num_local_experts`` = the experts held here, ``num_experts`` = the
router's width, ``block_length``), importing nothing of the program; tested
against hand-worked values. MFU convention: backward = 2 x forward,
recomputation under checkpointing NOT counted. A sequence of P prompt and R
completion tokens whose trace has K states is P + R TRAINED tokens, counted
once, and takes

- P + R + K x (R + P mod block) SLOTS of a row (the clean stream, and a noisy
  stream a state from the first block that holds a completion token): every
  layer's matmuls, experts and norms run over slots;
- KEPT (query, key) pairs under the mask that is causal between blocks and
  two-way inside one (``kept_pairs``): the attention core, 2 matmuls forward
  and 4 backward of ``head_dim`` a head and pair;
- R SCORED slots: the output head is needed there and nowhere else (the
  program runs it over every slot: PERF.md section 7).
"""

from benchmark.arith import load_peaks  # noqa: F401


def block_length(c):
    return int(c.get("block_length", 4))


def _block_sizes(n, size):
    """The sizes of the blocks of a sequence of ``n`` tokens, in order."""
    n, size = int(n), int(size)
    return [min(size, n - a) for a in range(0, n, size)]


def first_noisy_block(prompt, size):
    """The first block that holds a completion token."""
    return int(prompt) // int(size)


def slots(prompt, n, states, size):
    """What a sequence of ``n`` tokens, ``prompt`` of them the prompt, takes
    of a row under ``states`` denoising states."""
    prompt, n = int(prompt), int(n)
    return n + int(states) * (n - first_noisy_block(prompt, size) * size)


def stream_slots(prompt, n, states, size):
    return slots(prompt, n, states, size) - int(n)


def kept_pairs(prompt, n, states, size):
    """The (query, key) pairs the mask keeps in ONE layer: a slot at block b
    of its sequence keeps the b * size clean keys before its block and the
    slots of its own stream and block; the clean stream has every block, a
    noisy stream those from the first that holds a completion token."""
    sizes = _block_sizes(n, size)
    each = [s * (b * int(size) + s) for b, s in enumerate(sizes)]
    return sum(each) + int(states) * sum(
        each[first_noisy_block(prompt, size):]
    )


def causal_pairs(n):
    n = int(n)
    return n * (n + 1) // 2


def _widths(c):
    d, dh = c["hidden_size"], c["head_dim"]
    return d, c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh


def expert_pairs_per_slot(c):
    """Expected pairs a slot and layer routed to the experts held here."""
    return (
        c["num_experts_per_tok"] * c["num_local_experts"] / c["num_experts"]
    )


def layer_weights_per_slot(c):
    """Weight elements one slot's forward multiplies with in a layer: the
    attention projections, the router over all experts, the expected share
    of the held experts."""
    d, q, kv = _widths(c)
    return (
        d * q + 2 * d * kv + q * d + d * c["num_experts"]
        + expert_pairs_per_slot(c) * 3 * d * c["moe_intermediate_size"]
    )


def _layer_params(c, experts):
    d, q, kv = _widths(c)
    return (
        d * q + 2 * d * kv + q * d + d * c["num_experts"]
        + experts * 3 * d * c["moe_intermediate_size"]
        + 2 * d + 2 * c["head_dim"]
    )


def param_count(c):
    """Stored parameters of the share: the held experts, the router, two
    layer norms and the per-head q and k norms a layer; embedding, untied
    head and the final norm."""
    d = c["hidden_size"]
    return (
        c["num_hidden_layers"] * _layer_params(c, c["num_local_experts"])
        + 2 * d * c["vocab_size"] + d
    )


def published_param_counts(c):
    """(whole, active a token) of the model as published (the file's
    ``published`` depth, experts and vocabulary): 30.5 B and 3.3 B."""
    pub, d = c["published"], c["hidden_size"]
    ends = 2 * d * pub["vocab_size"] + d
    layers = pub["num_hidden_layers"]
    return (
        layers * _layer_params(c, pub["num_local_experts"]) + ends,
        layers * _layer_params(c, c["num_experts_per_tok"]) + ends,
    )


def core_flops_per_pair(c):
    """QK^T and PV of one kept pair over all query heads, one forward."""
    return 2 * 2.0 * c["num_attention_heads"] * c["head_dim"]


def forward_flops(c, prompts, seq_lens, states):
    """One forward over whole sequences: 2 x a layer's weights a SLOT, the
    core over kept pairs, the head over the scored (completion) tokens."""
    size, layers = block_length(c), c["num_hidden_layers"]
    n_slots = sum(slots(p, n, states, size) for p, n in zip(prompts, seq_lens))
    pairs = sum(
        kept_pairs(p, n, states, size) for p, n in zip(prompts, seq_lens)
    )
    scored = sum(int(n) - int(p) for p, n in zip(prompts, seq_lens))
    return (
        layers * (2.0 * n_slots * layer_weights_per_slot(c)
                  + pairs * core_flops_per_pair(c))
        + 2.0 * scored * c["hidden_size"] * c["vocab_size"]
    )


def train_step_flops(c, prompts, seq_lens, states, n_forward_only=1):
    """Forward + backward (2 x forward) + ``n_forward_only`` plain forwards
    (GRPO's one logprob recompute)."""
    return (3.0 + n_forward_only) * forward_flops(
        c, prompts, seq_lens, states
    )


def flops_per_trained_token(c, prompt, n, states, n_forward_only=1):
    """``train_step_flops`` of one sequence over its own P + R tokens."""
    return train_step_flops(c, [prompt], [n], states, n_forward_only) / int(n)


# ------------------------------------------------ what the kernels are asked --
def bd_core_kernel_flops(c, pairs, n_forward_only=1):
    """The attention core's matmul operations over ``pairs`` kept pairs (a
    ROW's, the program's ``bd_pairs`` of the train microbatches; every layer
    attends them) for the passes the trainer's programs execute: the logprob
    recompute and the forward at 2 matmuls a pair and head, one backward at
    4. The forward is NOT run again under checkpointing (the remat policy
    saves ``attn_out``). What a tile computes beyond the mask, and the
    scores the two backward kernels each form again, are not counted."""
    return (
        (n_forward_only + 1 + 2) * float(pairs) * c["num_hidden_layers"]
        * core_flops_per_pair(c)
    )


def expert_kernel_flops(c, routed_pairs, checkpointed, n_forward_only=1):
    """What the grouped-matmul kernel executes for ``routed_pairs`` (slot,
    expert) pairs routed to the experts held (summed over layers, the
    program's ``moe_assignments`` of the train microbatches): 3 matmuls a
    pair; the plain forwards, the gradient pass's forward, that forward
    again under checkpointing, the backward's two products a matmul."""
    passes = n_forward_only + 1 + (1 if checkpointed else 0) + 2
    return (
        passes * 2.0 * routed_pairs * 3
        * c["hidden_size"] * c["moe_intermediate_size"]
    )
