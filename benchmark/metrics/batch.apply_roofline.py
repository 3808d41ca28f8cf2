"""The batched apply's share of its roofline, in percent: the least bytes
the apply must move (computed from the shapes alone, so it reads the same
work whatever implements the apply), over the chip's HBM bandwidth, over
the apply's device time.  Memory bandwidth bounds it: the merge does no
floating-point work worth counting."""

from benchmark.metrics._programs import APPLY

WORD = 4           # int32
STATE_SLOT = 2     # elem_id, char: (D, S)
STATE_MARK = 8     # m_action .. m_attr: (D, M)
STATE_REG = 5      # r_obj .. r_val: (D, R)
STATE_SCALAR = 4   # num_slots, num_tombs, num_marks, num_regs: (D,)
INS_COLS = 3       # ins_ref, ins_op, ins_char: (D, K_ins)
MARK_COLS = 8      # ops/encode.MARK_COLS: (D, K_mark)
MAP_COLS = 5       # ops/packed.MAP_STREAM_COLS: (D, K_map)


def apply_bytes(docs, slots, marks, ins, dels, regs, maps):
    """State read and written once (tombstones sized to the delete stream,
    the overflow flag a byte), op streams and their counts read once."""
    state = docs * (WORD * (STATE_SLOT * slots + dels + STATE_MARK * marks
                            + STATE_REG * regs + STATE_SCALAR) + 1)
    streams = docs * WORD * (INS_COLS * ins + dels + MARK_COLS * marks
                             + MAP_COLS * maps + 2)
    return 2 * state + streams


def read(r):
    from benchmark.trace import program_seconds

    s = program_seconds(r.trace, APPLY, r.lo, r.hi)
    if not s:
        return None
    p = r.window["program"]
    per_merge = apply_bytes(r.window["docs"], p["slot_capacity"], p["mark_capacity"],
                            p["op_capacity"], p["op_capacity"], p.get("map_capacity", 32), 0)
    least = per_merge * r.window["merges"] / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
