"""Device time of the serving engine's compiled programs, one run at a time.

Since PR 25 the engine names what it hands to ``jax.jit``, so the ``XLA
Modules`` line of a trace reads ``jit_hvd_serve_decode`` and
``jit_hvd_serve_prefill`` and a run is told by its program's name. A program
built before that reads ``jit__unknown`` for every one of them; there a run
of such a program is told by what it holds, the rule
``prefill_ms_per_prompt_token`` uses: a decode run holds an
``hvd_paged_decode`` operation, a prefill run holds none, and the second says
nothing unless some run in the window is of the first kind. A program under
any other name (the ``jit_convert_element_type`` of a scalar's upload, 76 runs
of 0.6 us in a traced window, my chip run, PR 25) belongs to neither."""

from __future__ import annotations

from typing import List, Optional

ENGINE_PREFIX = "hvd_serve_"
UNNAMED = "jit__unknown"
DECODE_KERNEL = "hvd_paged_decode"


def run_seconds(summary, name: str, holds_kernel: bool) -> List[float]:
    """Seconds inside the window of each run, on the first device, of the
    programs whose name contains ``name``; where no program in the window
    carries an engine name, of the unnamed programs' runs that hold the
    decode kernel (``holds_kernel``) or hold none of it."""
    if any(ENGINE_PREFIX in program for program, _, _ in summary.programs):
        return [seconds for program, seconds, _ in summary.programs
                if name in program]
    by_content = [(seconds, any(DECODE_KERNEL in op for op in ops))
                  for program, seconds, ops in summary.programs
                  if program == UNNAMED]
    if not any(holds for _, holds in by_content):
        return []
    return [seconds for seconds, holds in by_content if holds == holds_kernel]


def ms_per_run(run, name: str, holds_kernel: bool) -> Optional[float]:
    """Mean device milliseconds of one run of that program; None where the
    run was not traced or the window holds no such run."""
    if run.trace is None:
        return None
    seconds = run_seconds(run.trace, name, holds_kernel)
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
