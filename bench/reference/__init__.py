"""Plain references of the models the benchmark serves and trains, one file
per architecture: ``bench/reference/<architecture>.py``, found by the
``architecture`` key of a configuration file (``harness.find_cell``).

A reference imports nothing of the program and takes nothing the program
made.  It reads the configuration file's published keys, and provides:

``init_weights(c, seed)``
    The weights from the seed, made on the device in one jitted call in the
    type they are served in, as the program's parameter tree.  Both the
    program and the reference are given them.
``flatten(tree)``
    The program's parameter tree (or a tree of its shape, such as an
    optimizer's moment) as a flat ``{leaf name: array}``.
``served_gaps(c, weights, prompt, served, width, n_out, control=False)``
    Per served token, the gap by which its logit lies below the
    reference's best at its position, given the request's own prompt from
    position 0 and the served tokens before it; and, per position, the gap
    of the token that the control (the reference one precision step down)
    puts first, where ``control`` is set.  ``width`` and ``n_out`` fix the
    shapes of every call.
``train_steps(c, opt, weights, batches, quant=None)``
    ``len(batches)`` optimizer steps from ``weights`` on ``(tokens,
    labels)`` batches: the loss of each step, the per-leaf norms of the
    first (clipped) gradient and of each leaf's change over all steps, by
    ``flatten``'s names.  ``quant="fp8"`` runs the control.
"""

INTERFACE = ("init_weights", "flatten", "served_gaps", "train_steps")
