"""Fused step: bytes one chip sends over the interconnect per train step
(the `collective_wire_bytes` arg of each main-lane `dispatch` span: the
compiled step's collectives as the program reads them from its partitioned
module, ring-algorithm wire bytes per device), mean over the window's
dispatches, in KB (1024 bytes). None where a dispatch lacks the arg: one
device, or a program that does not read its collectives."""


def read(ctx):
    wire = [ev.get("args", {}).get("collective_wire_bytes")
            for ev in ctx._spans if ev["name"] == "dispatch"]
    if not wire or None in wire:
        return None
    return sum(wire) / len(wire) / 1024
