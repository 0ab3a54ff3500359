"""Toy-width copies of the traffic files for the CPU rehearsals."""
import copy

from benchmark.harness import cells


def mlm(rows_per_chip=4, seq=32, masked=4):
    t = copy.deepcopy(cells.load_json("traffic", "mlm-s128-b256"))
    t["rows_per_chip"] = rows_per_chip
    by_name = {f["name"]: f for f in t["fields"]}
    by_name["input_ids"].update(shape=[seq], low=10)
    by_name["masked_positions"].update(shape=[masked], of=seq)
    by_name["mlm_labels"].update(shape=[masked], low=10)
    t["probe_steps"], t["trace_steps"] = 10, 3
    return t


def imagenet(rows_per_chip=4, hw=32):
    t = copy.deepcopy(cells.load_json("traffic", "imagenet-b256"))
    t["rows_per_chip"] = rows_per_chip
    t["fields"][0]["shape"] = [3, hw, hw]
    t["loader"]["num_workers"] = 2
    t["probe_steps"], t["trace_steps"] = 10, 2
    return t


def embed(rate=150.0, seq=32):
    t = copy.deepcopy(cells.load_json("traffic", "embed-open-r80"))
    t["fields"][0].update(shape=[seq], low=10)
    t.update(rate_per_s=rate, generators=2, connections=8, lead_s=2.0,
             drain_s=1.0, trace_from_s=0.3, trace_seconds=0.5)
    return t
