"""Which instruction of a compiled program is which Pallas kernel.

A profiler trace names a device op by its HLO instruction (``closed_call.43``)
and JAX's ``op_name`` for a Pallas call ends in ``pallas_call`` whatever the
kernel is. The kernel's function name is in the call itself: a Mosaic custom
call carries its kernel as MLIR bytecode (base64, under ``"body"``), whose
string table holds the function's name in clear. So the compiled step's own
text says which instruction runs ``_flash_fwd_kernel`` — no name has to be
given inside the program, and no guess is made from scopes or shapes.
"""

from __future__ import annotations

import base64
import math
import re

CALL = re.compile(
    r'^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<out>[^\n]*?) custom-call\('
    r'[^\n]*custom_call_target="tpu_custom_call"[^\n]*', re.M)
BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
KERNEL = re.compile(rb"_[A-Za-z0-9_]*_kernel[A-Za-z0-9_]*")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def pallas_calls(hlo_text: str) -> dict[str, dict]:
    """``{instruction: {"kernel": function name, "out_elements": elements of
    the call's first output}}`` for every Mosaic custom call of the program.
    The first output of the flash kernels is O (forward) and dQ (backward):
    rows x seq x heads x head_dim elements in whatever layout."""
    calls = {}
    for m in CALL.finditer(hlo_text):
        body = BODY.search(m.group(0))
        names = KERNEL.findall(base64.b64decode(body.group(1))) if body else []
        shape = SHAPE.search(m.group("out"))
        dims = [int(d) for d in shape.group(1).split(",") if d] if shape else []
        calls[m.group("name")] = {
            "kernel": names[0].decode() if names else "pallas_call",
            "out_elements": math.prod(dims)}
    return calls


def instruction(event_name: str) -> str:
    """``%closed_call.43 = (...) custom-call(...)`` -> ``closed_call.43``."""
    return event_name.split(" = ")[0].strip().lstrip("%")
