"""Plain float32 references of the served models.

Each configuration names its module here (its ``"reference"`` key,
``bench/reference/<reference>.py``), which exposes ``Reference(dims,
params, *, seq_len, decode_nf4, low_precision)`` with ``.logits(prompt,
served)``.  ``nf4.py`` is shared: NF4 written from its definition."""
