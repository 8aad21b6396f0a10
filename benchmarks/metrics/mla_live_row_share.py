"""Latent rows the attention needed over the rows it read, per attention block (the family's
counts on the host, in the window): ``.decode``, each live slot's cached length with its new
token over the slots times ``max_seq`` a step reads (the gather of every slot's block table);
``.prefill``, ``start + n_real`` of each chunk over the ``max_seq`` rows its gather reads and
expands. A kernel that follows the block table over the live pages moves it to 1."""


def read_part(run, part):
    live = run.counters.get(f"mla_rows_{part}_live")
    read = run.counters.get(f"mla_rows_{part}_read")
    if not live or not read:
        return None
    return live / read


def example(run):
    """Four decode steps of 32 slots x 12800 rows, 600 000 of them live; three chunks read
    12800 rows each, 9000 live."""
    run.counters.update(mla_rows_decode_live=600_000, mla_rows_decode_read=4 * 32 * 12800,
                        mla_rows_prefill_live=9000, mla_rows_prefill_read=3 * 12800)
