"""Share of the block table's pages that the window's paged decode steps
read: ``repro_paged_kv_pages_read_total`` (each slot's live pages,
``ceil((cache_len + 1) / block_size)``) over
``repro_paged_kv_table_pages_total`` (every page of every slot's table,
what a gather of the whole table reads), both counted once per decode
step. The counters are the scheduler's ``PagedKVReads`` (observability
on, ``--trace 1``); a program without them reads None."""

READ = "repro_paged_kv_pages_read_total"
TABLE = "repro_paged_kv_table_pages_total"


def _delta(run, key):
    a = run["status0"].get("metrics", {}).get("counters", {}).get(key)
    b = run["status1"].get("metrics", {}).get("counters", {}).get(key)
    return None if a is None or b is None else b - a


def read(run):
    pages, table = _delta(run, READ), _delta(run, TABLE)
    if pages is None or not table:
        return None
    return 100.0 * pages / table
