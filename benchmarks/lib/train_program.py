"""What every family's ``TrainProgram`` shares: reading the first gradient
out of the optimizer's state and the parameters' change since a snapshot, and
what it hands the traffic kind for the record: ``facts()`` (merged into
``rec.program``: the shapes a roofline needs), ``hlo_texts()`` (label ->
compiled text of every program the window runs, for the trace's scopes) and
``counters()`` (running totals of the family's own; the kind stores what the
window added to each under ``rec.counters``)."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.lib import trees


class TrainProgramBase:
    stacked: Tuple[str, ...] = ()
    _start: Any = None
    main_program = "train_step"     # whose text ``rec.program["hlo_text"]`` is
    compiled: Any = None            # the step, AOT-compiled

    def facts(self) -> Dict[str, Any]:
        return {"shapes": {}}

    def hlo_texts(self) -> Dict[str, str]:
        return {self.main_program: self.compiled.as_text()}

    def counters(self) -> Dict[str, Any]:
        return {}

    def params(self) -> Any:
        raise NotImplementedError

    def opt_state(self) -> Any:
        raise NotImplementedError

    def further(self) -> Any:
        """State beside the parameters that a step moves (running batch
        statistics), or None."""
        return None

    def snapshot(self) -> None:
        self._start = jax.tree.map(jnp.copy, (self.params(), self.further()))

    def first_gradient_norms(self) -> Dict[str, float]:
        """After exactly one step of SGD with momentum the trace IS the
        gradient the optimizer was given."""
        import optax
        is_trace = lambda s: isinstance(s, optax.TraceState)  # noqa: E731
        traces = [s for s in jax.tree.leaves(self.opt_state(),
                                             is_leaf=is_trace)
                  if is_trace(s)]
        if len(traces) != 1:
            raise RuntimeError(f"expected one momentum trace in the "
                               f"optimizer state, found {len(traces)}")
        return trees.leaf_norms(traces[0].trace, self.stacked)

    def change_norms(self) -> Dict[str, Dict[str, float]]:
        """Per-leaf norms of the change since ``snapshot``: ``"delta"`` of
        the parameters and, where there is further state, ``"stats"``."""
        params, further = self._start
        out = {"delta": trees.leaf_norms(
            trees.tree_sub(self.params(), params), self.stacked)}
        if further is not None:
            out["stats"] = trees.leaf_norms(
                trees.tree_sub(self.further(), further))
        self._start = None
        return out
