"""Own device milliseconds of one train step by the program's scopes: ``.attention``
(``hvd_attention``, forward and backward: the flash kernels), ``.mlp`` (``hvd_mlp``), ``.loss``
(``hvd_loss``), ``.optimizer`` (``hvd_optimizer``), ``.grad_sync`` (``hvd_grad_sync``: the
gradient all-reduce, where there are chips to exchange with) and ``.other``: projections,
norms, the layer stack's copies. The parts add up to the step's busy time."""
from benchmarks.lib import readers

PROGRAM = "train_step"
SCOPES = {"attention": "hvd_attention", "mlp": "hvd_mlp", "loss": "hvd_loss",
          "optimizer": "hvd_optimizer", "grad_sync": "hvd_grad_sync"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)


def example(run):
    """The all-reduce of a step on several chips, under its scope."""
    from benchmarks.lib import trace
    step = run.trace.scope_op_s.setdefault("jit_train_step", {trace.UNSCOPED: {"fusion": 0.2}})
    step["hvd_grad_sync"] = {"psum": 0.11}
    if not run.trace.program_runs(PROGRAM):
        run.trace.programs.append(("jit_train_step", 0.3, frozenset({"fusion", "psum"})))
