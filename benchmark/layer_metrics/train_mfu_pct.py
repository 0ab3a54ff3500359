"""Model FLOP/s utilisation of the train cell: samples per second times the
configuration's ``flops_per_sample`` (from shapes; nothing recomputed is
counted), over chips times the published bf16 peak of the device."""
LAYER = "model code (text/models.py, vision/models/resnet.py, nn/)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    if "flops_per_sample" not in record or "steps" not in record:
        return None
    w = record["window"]
    samples_per_s = record["steps"] * record["rows_per_step"] / w["seconds"]
    peak = record["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * samples_per_s * record["flops_per_sample"] / peak
