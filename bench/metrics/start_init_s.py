"""Mean seconds of the program's ``engine.init`` span in each start of the
traced window: what every ``cp_als`` call pays before its first sweep."""


def read(run):
    inits = [s.duration_ns for s in run.spans if s.name == "engine.init"]
    return sum(inits) / len(inits) * 1e-9 if inits else None
