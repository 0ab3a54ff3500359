"""Share of the window the train loop spent getting the next batch from the
DataLoader and placing it on the devices (the benchmark's ``next_batch``
and ``shard_batch`` spans). The loop runs one step ahead of the device, so
a share below about one step in the window costs nothing; above it the
input pipeline sets the pace."""
LAYER = "input pipeline (io/dataloader.py, spmd.shard_batch)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    if "step_done" not in record:
        return None
    w = record["window"]
    waited = sum(t1 - t0 for name, t0, t1 in record["spans"]
                 if name in ("next_batch", "shard_batch")
                 and w["start"] <= t0 < w["end"])
    return 100.0 * waited / w["seconds"]
