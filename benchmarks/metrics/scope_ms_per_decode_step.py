"""Own device milliseconds of one run of the engine's decode program by the program's scopes:
``.attention`` (``hvd_attention``: the paged-decode kernel and what feeds it), ``.mlp``
(``hvd_mlp``), ``.kv_write`` (``hvd_kv_write``: the new token's keys and values scattered into
the pool) and ``.other``: the projections, norms, casts and the head, which lie under no scope.
The parts add up to the program's busy time a run."""
from benchmarks.lib import readers

PROGRAM = "hvd_serve_decode"
SCOPES = {"attention": "hvd_attention", "mlp": "hvd_mlp", "kv_write": "hvd_kv_write"}


def read_part(run, part):
    return readers.scope_ms_per_run(run, PROGRAM, SCOPES, part)
