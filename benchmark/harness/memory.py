"""Device memory of a run: what the runtime counted, and what the compiled
programs need."""


def program_memory(name, compiled):
    """The compiler's own account of one program (``memory_analysis()``)."""
    m = compiled.memory_analysis()
    rec = {"name": name,
           "argument_bytes": int(m.argument_size_in_bytes),
           "output_bytes": int(m.output_size_in_bytes),
           "alias_bytes": int(m.alias_size_in_bytes),
           "temp_bytes": int(m.temp_size_in_bytes),
           "code_bytes": int(m.generated_code_size_in_bytes)}
    # arguments + temporaries + the outputs that do not reuse an argument
    rec["footprint_bytes"] = (rec["argument_bytes"] + rec["temp_bytes"]
                              + rec["output_bytes"] - rec["alias_bytes"])
    return rec


def runtime_stats(devices):
    return [dict(d.memory_stats() or {}) for d in devices]


def peak_bytes(stats, programs):
    """The peak on the fullest chip. The runtime's ``peak_bytes_in_use``
    counts the arrays the process holds; on this libtpu it does not count
    the temporaries a running program allocates (PR 21: 1.6 GB read after
    steps whose program needs 11.9 GB of them, PERF.md section 7). So the
    peak is the larger of the runtime's counter and the largest program's
    own footprint while it runs, which the compiler states exactly."""
    counted = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    compiled = max((p["footprint_bytes"] for p in programs), default=0)
    return max(counted, compiled)
