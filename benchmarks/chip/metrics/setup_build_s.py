"""Host seconds of set-up spent building the rules' host-side tables: the
program's ``simx.build`` span total, less the compile seconds that ran
inside it (``setup_compile_s`` counts those; ``repro.simx.spans``)."""

import stages


def read(w):
    spans = stages.program_spans()
    if spans is None:
        return None
    return spans.totals["simx.build"] - spans.compile_in["simx.build"]
