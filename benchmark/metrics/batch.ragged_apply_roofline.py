"""The ragged apply's share of its roofline, in percent: the least bytes
the apply must move, computed from the histories' true counts alone (so it
reads the same work whatever implements the apply), over the chip's HBM
bandwidth, over the apply's device time.  Memory bandwidth bounds it: the
merge does no floating-point work worth counting."""

WORD = 4            # int32
ELEM_COLS = 2       # elem_id, char: each final element written once
INS_COLS = 3        # ins_ref, ins_op, ins_char: each insert read once
RAGGED_APPLY = ("jit_apply_batch_ragged",)  # as batch.ragged_apply_device_ms


def apply_bytes(docs, inserts, deletes):
    """Per merge: every element (id, char) and every tombstone written
    once, the insert and delete streams read once."""
    return docs * WORD * ((ELEM_COLS + INS_COLS) * inserts + 2 * deletes)


def read(r):
    from benchmark.trace import program_seconds

    s = program_seconds(r.trace, RAGGED_APPLY, r.lo, r.hi)
    if not s:
        return None
    sizes = r.config["sizes"]
    per_merge = apply_bytes(r.window["docs"], sizes["inserts"], sizes["deletes"])
    least = per_merge * r.window["merges"] / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
