import sys

from chipbench.run import main

sys.exit(main())
