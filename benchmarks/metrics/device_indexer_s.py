"""Device self time under scope `indexer` in the traced generation
(the index scorer: its projections, index scores, the selection and the indexer's loss): forward, backward and evaluation
together. Nothing where the program opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("indexer") if red else None
