"""How the gf2_bmvm Pallas kernel shows in the device trace.

An XLA op event is named by its HLO text.  The kernel is a ``custom-call``
to ``tpu_custom_call`` (Mosaic), and the instruction takes the name of the
jitted function that wraps it, ``gf2_bmvm_pallas``:
``%gf2_bmvm_pallas.2 = u32[1024,512]{...} custom-call(...),
custom_call_target="tpu_custom_call"``.
"""
from bench.trace import opcode


def is_gf2_bmvm(name: str) -> bool:
    return (name.startswith("%gf2_bmvm") and opcode(name) == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in name)
