"""Mean host gap between a served search's consecutive segments, in
milliseconds: the window's delta of the program's
`tts_segment_gap_seconds` histogram (sum over count)."""


def read(run):
    n = run.counters.get("segment_gaps", 0)
    if not n:
        return None
    return 1000.0 * run.counters["segment_gap_s"] / n
