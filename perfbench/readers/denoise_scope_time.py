"""``scope_time_words`` for the cells of a model that generates by blocks:
the same reading of the capture (``params.words`` names the scope words
beyond the accepted readers' list: ``tpudist/scopes.py``'s ``MODEL_SCOPES``
and ``BLOCK_SCOPES``), under a reader name of its own because an accepted
test holds every metric of THAT reader's name to the ``cohere2moe`` cell
and to its word list (``perfbench/tests/test_cohere2moe_cell.py``), and a
later PR may not edit it."""

from perfbench.readers.scope_time_words import parse, read  # noqa: F401
