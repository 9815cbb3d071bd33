from .linkbench import LinkBenchConfig, LinkBenchWorkload, REQUEST_MIX
from .pipeline import GraphStream
