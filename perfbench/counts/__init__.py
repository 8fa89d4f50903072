"""Operations and bytes that each kernel or step needs per call, from its
shapes: one module per kernel or step, each with ``flops_bytes``.

Counted is the work the algorithm needs, never padding: busy rows only,
each row's valid context, and causal work over the real prompt length.
``cfg`` is a configuration file's mapping (published key names).
"""
