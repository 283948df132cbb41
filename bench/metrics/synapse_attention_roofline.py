"""synapse_attention_roofline: least time of the traced synapse_attention
calls at their true shapes (lanes × landmark+window+inject slots × d_head)
over their summed device time, in percent of the roofline."""
from bench import readers


def read(run):
    return readers.kernel_roofline(run, "synapse_attention", readers.synapse_call)
