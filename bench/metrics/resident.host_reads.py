"""resident.host_reads: device-to-host reads per resident decode step,
counted by the program (``EngineStats.resident_host_reads /
resident_steps``) over the whole run: each step's flush reads the cache
lengths and the page table, and on a page boundary each leaf's page escape
counts. Moves resident_tpot_ms."""

from bench import spans


def read(ctx):
    return spans.ratio(ctx.counters, "resident_host_reads", "resident_steps")
