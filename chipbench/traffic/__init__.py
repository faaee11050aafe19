"""Traffic generators: every input of a run is a pure function of ``--seed``."""
