"""Gigabytes of per-slot gated DeltaNet state and convolution tails the engine holds on the
device, as they lie there with their tiles padded (``engine.stats()["ssm"]["resident_bytes"]``):
fixed by slots and layers, whatever the requests' lengths. Equal to the bytes stored
(``state_bytes``) in the layout of two heads of 192 values on 384 lanes."""


def read(run):
    held = run.program.get("ssm_resident_bytes")
    return held / 1e9 if held else None


def example(run):
    run.program["ssm_resident_bytes"] = 902_430_720
