"""What every cell shares: the run itself, trace reduction, work counts
and the table of peaks."""
