"""How many queries each tile the row-window kernel streams serves: the
per-query Eq. 2 window tiles over the query-block union tiles the
kernel's grid scans, from the service's stage-1 tile counter. A program
without that counter gives no reading."""


def read(run):
    window = run.reg("service_stage1_tiles_total", kind="window")
    streamed = run.reg("service_stage1_tiles_total", kind="streamed")
    if window <= 0 or streamed <= 0:
        return None
    return window / streamed
