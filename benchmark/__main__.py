import sys

from benchmark.run import main

if __name__ == "__main__":
    sys.exit(main())
