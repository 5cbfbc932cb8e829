"""Reader ``unit_count`` (the tests' own): how many whole units the untraced
window held. Shows that a per-layer metric and its reader are two new files."""


def read(observed, args, ctx):
    return len(observed.get("units", []))
