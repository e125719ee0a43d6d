"""Device self time under `train_segment` / `map_members` and none of
forward, backward, optimizer or input: member-chunk stitching, loop
carries, copies."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["phase"]["train_rest"] if red else None
