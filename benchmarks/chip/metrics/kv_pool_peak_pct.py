"""Share of the KV pool's blocks in use at the window's high-water mark
(the block allocator's counter, reset as the window opens)."""


def read(rec):
    if not rec["kv_blocks"]:
        return None
    return 100.0 * rec["kv_peak_blocks"] / rec["kv_blocks"]
