"""A kind of layer's record, outside ``models/`` and ``moe/`` and reading nothing of the package: ``models/`` imports
``moe/`` for its table (``models/transformer.py::MIXERS``, ``FFNS``) and the kinds of both mix this in."""


class LayerKind:
    """A kind of layer's record: what its hosts read of it and never work out from its name. A kind IS its flax module with
    this class mixed in, saying only where it differs, under one name in the table (``models/transformer.py``). A mixer is
    called ``mixer(h, positions, kv_cache, segment_ids)`` and returns what ``Attention`` returns; an FFN ``ffn(h, train)``."""

    sows = ()  # the collections it may write (``Module.sow``): a model with such a layer traces its loss with them mutable
    # ``report(intermediates)`` hands what the model's layers of this kind sowed in a forward pass to ``telemetry/
    # device_counts.py`` and returns the kind's own loss (the step's loss takes its gradient and not its value) or None
    report = None
    keeps = ()  # the names its own ``checkpoint_name``s give, its kernels' among them
    # a checkpointed block with it keeps, by name, what its two parts' ``keeps`` say; else what they say without the
    # projections' name: its kernels' outputs, beside its inputs (``models/transformer.py::remat_keeps``)
    hybrid = False
    # the trainer's first-call line. ``paths``: key -> (region, labels) of ``program_regions_traced_total``; the key's
    # word is ``xla`` where only ``path="xla"`` call sites rose, ``mixed``, else ``kernel`` (or ``path_words[key]``).
    # ``joined``: key -> (region, the ``path`` labels of it that may rise[, another label than ``path`` whose values they
    # are[, labels a series must also carry to be read]]): the word is those that rose, "+" between; ``None`` for the
    # labels: whatever values the sites gave (a number a site worked out, as the tiles a mask's walk visits). A model of
    # one plain kind says its joined keys too, where they rose (the flash kernels' ``tiles_a_trip_fwd``).
    # ``alone``: a model whose layers are all of one kind says nothing of kinds on that line, unless this
    paths, path_words, joined, alone = {}, {}, {}, False
    stackable = False  # the scan over layers, ``to_pipeline`` and ``inference/v2`` can run it
    # values handed to a part, by name. A MIXER's lie between blocks. ``gives``: its call returns ``(out, {name: value})``
    # and the model's loop over layers carries each value on; ``takes``: it is called with ``name=value`` of the nearest
    # earlier layer that gave the name (``layer``, the layer's own published index as an int32 scalar, is the model's to
    # give). The gradient flows back through them. A model with either is run by the unrolled loop alone. An FFN gives
    # nothing and ``takes`` what its OWN block made ahead of the mixer (``transformer.py::Block``: ``mixer_input``, the
    # first norm's output, which a router placed ahead of the attention scores): a value inside one block's trace, so
    # ``block_fn``, a checkpointed block (which makes the norm again from its input) and ZeRO-3's gathered block carry it
    # with no argument of their own, and the stacked forms are not concerned
    gives, takes = (), ()
    # ``targets(cfg, input_ids)``: a kind whose OBJECTIVE is not next-token prediction over the whole row gives the loss
    # head (the hidden states' positions it runs over, their targets, a float32 weight a target, what the weighted sum is
    # divided by), all made from the ids (``CausalLM.loss_fn``); None: the model's own (every position predicts the next)
    targets = None

    @classmethod
    def from_config(cls, cfg, kind: str):
        """The module of a block of ``cfg`` for the table's name ``kind``, under its name in the parameter tree."""
        return cls(cfg, name=kind)

    def no_cache(self, kv_cache, segment_ids):
        if kv_cache is not None or segment_ids is not None:  # a training-side mixer's refusal
            raise NotImplementedError(f"a {self.name} layer takes no KV cache and no packed segments yet")
