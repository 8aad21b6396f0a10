"""Gigabytes of per-slot delta-rule state and convolution tails the engine holds on the device
(``engine.stats()["ssm"]["state_bytes"]``): fixed by slots and layers, whatever the requests'
lengths."""


def read(run):
    held = run.program.get("ssm_state_bytes")
    return held / 1e9 if held else None


def example(run):
    run.program["ssm_state_bytes"] = 1_724_000_000
