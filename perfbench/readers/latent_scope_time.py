"""``scope_time_words`` for the cells of a model with latent attention and
identity experts: the same reading of the capture (``params.words`` names
the scope words beyond the accepted readers' list: ``tpudist/scopes.py``'s
``MODEL_SCOPES``, ``BLOCK_SCOPES`` and ``LATENT_SCOPES``), under a reader
name of its own because accepted tests hold every metric of the other two
names to their cells and word lists, and a later PR may not edit them."""

from perfbench.readers.scope_time_words import parse, read  # noqa: F401
