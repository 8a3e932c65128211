"""Share (%) of requests due inside the window that met the TTFT limit and
showed no inter-token gap above the ITL limit by the window's end. A
rejected request misses; an unfinished one is judged on what it showed,
including its wait since its last token. Host clock."""


def met(r, ttft, itl, end) -> bool:
    if r.rejected:
        return False
    first = r.stamps[0] if r.stamps else end
    if first - r.due > ttft:
        return False
    gaps = [b - a for a, b in zip(r.stamps, r.stamps[1:])]
    if r.stamps and not r.req.done:
        gaps.append(end - r.stamps[-1])
    return all(g <= itl for g in gaps)


def read(run):
    reqs = run.due_in_window()
    if not reqs:
        return None
    p = run.cell.params
    ok = sum(met(r, p["ttft_limit_s"], p["itl_limit_s"], run.w1)
             for r in reqs)
    return 100.0 * ok / len(reqs)
