"""The yardstick's shared arithmetic: trace readers and reduction, the FLOPs
walk, the table of peaks, percentiles. Only ``hostlib`` touches the program."""
