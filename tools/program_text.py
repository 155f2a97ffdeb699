"""A trainer program's OPTIMIZED text without what only names it, to show
that a change of names (``tracing.device_scope``) left the program alone:

    python -m tools.program_text <out file>

compiles the dense cell's ``grad_step`` (``qwen2_0p5b``, float32 parameters,
bf16 compute, one packed row of 4,096 tokens, gradient checkpointing on) for
the chip this process holds, writes the compiled HLO text stripped of every
``metadata={..}``, every ``frontend_attributes={..}`` and the source tables,
and prints two digests: of the stripped text with each Pallas kernel's
serialized body cut out, and of those bodies decoded and printed WITHOUT
their debug locations (a kernel's body carries its callers' file names and
line numbers, so the bytes move with any edit above the call; the operations
do not). Run it at the parent (``git archive``, its ``areal_tpu/`` in the
root) and at the change: equal digests are the same program.
"""

import base64
import hashlib
import re
import sys

_TABLES = re.compile(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", re.S)
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _without(text: str, key: str) -> str:
    """``text`` with every ``, key={...}`` (balanced braces) removed."""
    out, at = [], 0
    for m in re.finditer(r",? ?" + key + r"=\{", text):
        if m.start() < at:  # inside one already cut
            continue
        out.append(text[at:m.start()])
        at, depth = m.end(), 1
        while depth:
            depth += (text[at] == "{") - (text[at] == "}")
            at += 1
    return "".join(out) + text[at:]


def stripped(text: str) -> str:
    """A compiled module's text without the names: no source tables, no
    ``metadata``, no ``frontend_attributes`` on an instruction (the module
    line keeps its own: the mesh is not a name)."""
    head, _, body = _TABLES.sub("\n", text).partition("\n")
    return head + "\n" + _without(
        _without(body, "metadata"), "frontend_attributes"
    )


def kernel_bodies(text: str):
    """(``text`` with every Pallas kernel's serialized body cut out, the
    bodies as MLIR text without debug locations, in the text's order)."""
    from jax.extend.mlir import ir
    from jax.interpreters import mlir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    bodies = []
    for m in _BODY.finditer(text):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    return _BODY.sub('"body":""', text), bodies


def digests(text: str):
    """(the sha256 of ``stripped(text)`` with its kernel bodies cut out, the
    sha256 of those bodies without their locations)."""
    cut, bodies = kernel_bodies(stripped(text))
    return tuple(
        hashlib.sha256(s.encode()).hexdigest()
        for s in (cut, "\n".join(bodies))
    )


def main():
    (out,) = sys.argv[1:]

    from tools import setup_cost  # first: areal_tpu before the backend

    import jax
    import jax.numpy as jnp

    grad_step, a = setup_cost.cell_programs(
        "qwen2_0p5b", 4096, param_dtype=jnp.float32, devices=jax.devices()
    )["grad_step"]
    text = grad_step.lower(*a).compile().as_text()
    with open(out, "w") as f:
        f.write(stripped(text))
    cut, bodies = digests(text)
    scopes = sorted(set(re.findall(r'areal_scope="([^"]+)"', text)))
    print(
        f"{out}: text without kernel bodies {cut}; kernel bodies without "
        f"locations {bodies}; scopes in the unstripped text: {scopes}",
        flush=True,
    )


if __name__ == "__main__":
    main()
