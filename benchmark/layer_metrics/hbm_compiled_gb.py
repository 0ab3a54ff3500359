"""What the cell's largest program needs of the device's memory, by the
compiler's own account (``memory_analysis()``): arguments + temporaries +
the outputs that do not reuse an argument. A question of fit, not of speed:
it bounds the batch a chip can train."""
LAYER = "device memory (XLA buffer assignment)"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "train_samples_per_s"


def read(record):
    programs = record.get("programs")
    if not programs:
        return None
    return max(p["footprint_bytes"] for p in programs) / 1e9
