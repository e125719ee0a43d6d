"""Share of the traced generation's device self time whose path carries
one of the program's scopes. What is left has no name."""

import scopes


def read(run):
    red = scopes.for_run(run)
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * red["scoped_s"] / red["busy_s"]
