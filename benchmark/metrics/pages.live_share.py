"""Mean share of the page pool that held live pages over the decode steps
(tags ``live_pages`` / ``pages`` of the program's span
``hvd.engine.decode_step``, recorded under ``HOROVOD_TRACE``). None where the
program recorded no such span."""

from benchmark.lib import program_spans


def read(r):
    shares = [
        rec["tags"]["live_pages"] / rec["tags"]["pages"]
        for rec in program_spans.snapshot(r)
        if rec.get("name") == "hvd.engine.decode_step"
        and rec.get("tags", {}).get("pages")
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
