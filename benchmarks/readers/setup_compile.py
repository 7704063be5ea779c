"""Seconds from the program's import until the first measured sync
point, less the corpus: the first call of each jitted program until
ready (a cache load on a warm run, a compile on a cold one) plus the
warm-up steps or calls."""


def read(ctx):
    s = ctx["setup"]
    if "setup_s" not in s or "setup_start_s" not in s:
        return None
    return (s["setup_s"] - s["setup_start_s"] - s.get("corpus_s", 0.0)
            - s.get("import_program_s", 0.0))
