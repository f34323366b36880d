"""Operation and byte counts of the kernels and of the whole model step,
from the configuration's shapes and the attended lengths.  One module per
kernel; each counts the algorithm's work, never padded pages or tiles."""
